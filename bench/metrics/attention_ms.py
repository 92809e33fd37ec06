"""Device time per step of Luong attention outside a fused decoder scan
(scope ``attention``), forward and backward."""
from bench import readers

SCOPES = ("attention",)


def read(r):
    return readers.layer_ms(r, SCOPES)
