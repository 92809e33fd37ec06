"""Device time per step of the ``lstm_scan`` kernel, forward and backward."""
from bench import readers


def read(r):
    return readers.kernel_ms(r, "lstm_scan")
