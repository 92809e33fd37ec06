"""Device time per step of the optimizer (scopes ``grad_clip`` and
``adamw``): clipping the gradient to its global norm, the AdamW update
and its application to the parameters."""
from bench import readers

SCOPES = ("grad_clip", "adamw")


def read(r):
    return readers.layer_ms(r, SCOPES)
