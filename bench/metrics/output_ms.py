"""Device time per step of the output layer (scope ``output``): output
dropout and the vocabulary projection, forward and backward."""
from bench import readers

SCOPES = ("output",)


def read(r):
    return readers.layer_ms(r, SCOPES)
