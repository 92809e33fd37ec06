"""Device time per step of the loss (scope ``loss``): log-softmax, NLL
and the (masked) mean, forward and backward."""
from bench import readers

SCOPES = ("loss",)


def read(r):
    return readers.layer_ms(r, SCOPES)
