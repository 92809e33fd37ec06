"""Device time per step of Phase A (scope ``phase_a``): per recurrent
layer, the mask schedules and the time-batched non-recurrent matmul
before the scan, forward and backward."""
from bench import readers

SCOPES = ("phase_a",)


def read(r):
    return readers.layer_ms(r, SCOPES)
