"""The whole step's share of the chip's bf16 peak: the matmul FLOPs the
steps of the traced window need, over its length and the peak."""


def read(r):
    w = r.trace["window_s"]
    if not (w > 0 and r.steps and r.flops > 0):
        return None
    return 100.0 * r.flops * r.steps / w / r.peak["flops"]
