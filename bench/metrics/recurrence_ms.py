"""Device time per step of the recurrence: the nested jits that run the
scans, ``jit(cell_scan)`` and ``jit(_decoder_scan)``, on the xla and the
Pallas path alike, forward and backward."""
from bench import readers

SCOPES = ("jit(cell_scan)", "jit(_decoder_scan)")


def read(r):
    return readers.layer_ms(r, SCOPES)
