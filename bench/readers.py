"""What the per-layer metric readers (``metrics/<name>.py``) read.

A ``Reading`` holds one traced window: the trace's reduction
(``trace.reduce``), the number of steps in it, the host time of each of
those steps, the work one step needs (``configs/<config>.py``) and the
chip's peaks. Each reader is a module with ``read(r) -> float | None``;
None means the cell has nothing for it to read, and the metric is left
out of the run's line.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional


@dataclasses.dataclass
class Reading:
    trace: dict            # trace.reduce(...)
    steps: int             # steps whose spans lie in the traced window
    host_ms: List[float]   # per step: previous loss sync to dispatch done
    flops: float           # required FLOPs of one step
    work: dict             # {kernel: (flops, bytes)} of one step
    peak: dict             # peaks.peak(device_kind)


def kernel_ms(r: Reading, kernel: str) -> Optional[float]:
    """Device ms per step of ``kernel``'s ops; None where it did not run."""
    s = r.trace["kernel_s"].get(kernel, 0.0)
    return 1e3 * s / r.steps if s > 0 and r.steps else None


def kernel_roofline_pct(r: Reading, kernel: str) -> Optional[float]:
    """The least time the chip could take for the kernel's work, the
    larger of FLOPs over peak and bytes over HBM bandwidth, as a share of
    its measured time."""
    ms = kernel_ms(r, kernel)
    if ms is None or kernel not in r.work:
        return None
    flops, nbytes = r.work[kernel]
    least = max(flops / r.peak["flops"], nbytes / r.peak["hbm_bytes_per_s"])
    return 100.0 * least * 1e3 / ms


def roofline_bound(r: Reading, kernel: str) -> str:
    flops, nbytes = r.work[kernel]
    return ("compute" if flops / r.peak["flops"]
            >= nbytes / r.peak["hbm_bytes_per_s"] else "memory")
