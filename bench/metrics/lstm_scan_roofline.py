"""Share of its roofline that the ``lstm_scan`` kernel reaches: the larger
of its required FLOPs over peak and its required bytes over HBM
bandwidth, over its measured device time."""
from bench import readers


def read(r):
    return readers.kernel_roofline_pct(r, "lstm_scan")
