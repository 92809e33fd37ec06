"""Device time per step of the embedding layer (scope ``embed``): the
lookup and its dropout, forward and backward, and what it alone feeds."""
from bench import readers

SCOPES = ("embed",)


def read(r):
    return readers.layer_ms(r, SCOPES)
