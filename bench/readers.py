"""What the per-layer metric readers (``metrics/<name>.py``) read.

A ``Reading`` holds one traced window: the trace's reduction
(``trace.reduce``), the number of steps in it, the host time of each of
those steps, the work one step needs (``configs/<config>.py``) and the
chip's peaks. Each reader is a module with ``read(r) -> float | None``;
None means the cell has nothing for it to read, and the metric is left
out of the run's line. A reader of device time by layer lists the layer
names it reads in ``SCOPES`` (the names under the jitted step that
``trace.scope_of`` reads from each op's op_name); the run attributes
device time to the names that its metrics list.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence


@dataclasses.dataclass
class Reading:
    trace: dict            # trace.reduce(...)
    steps: int             # steps whose spans lie in the traced window
    host_ms: List[float]   # per step: previous loss sync to dispatch done
    flops: float           # required FLOPs of one step
    work: dict             # {kernel or layer: (flops, bytes)} of a step
    peak: dict             # peaks.peak(device_kind)


def kernel_ms(r: Reading, kernel: str) -> Optional[float]:
    """Device ms per step of ``kernel``'s ops; None where it did not run."""
    s = r.trace["kernel_s"].get(kernel, 0.0)
    return 1e3 * s / r.steps if s > 0 and r.steps else None


def layer_ms(r: Reading, names: Sequence[str]) -> Optional[float]:
    """Device self ms per step of the layers ``names`` (``trace.reduce``'s
    ``layer_s``); None where none of them ran."""
    s = sum(r.trace["layer_s"].get(n, 0.0) for n in names)
    return 1e3 * s / r.steps if s > 0 and r.steps else None


def _roofline_pct(r: Reading, work: str, ms: Optional[float]):
    if ms is None or work not in r.work:
        return None
    flops, nbytes = r.work[work]
    least = max(flops / r.peak["flops"], nbytes / r.peak["hbm_bytes_per_s"])
    return 100.0 * least * 1e3 / ms


def kernel_roofline_pct(r: Reading, kernel: str) -> Optional[float]:
    """The least time the chip could take for the kernel's work, the
    larger of FLOPs over peak and bytes over HBM bandwidth, as a share of
    its measured time."""
    return _roofline_pct(r, kernel, kernel_ms(r, kernel))


def layer_roofline_pct(r: Reading, name: str) -> Optional[float]:
    """The same share for the layer ``name``, whose work a configuration's
    ``kernel_work`` gives under that name."""
    return _roofline_pct(r, name, layer_ms(r, [name]))


def roofline_bound(r: Reading, kernel: str) -> str:
    flops, nbytes = r.work[kernel]
    return ("compute" if flops / r.peak["flops"]
            >= nbytes / r.peak["hbm_bytes_per_s"] else "memory")
