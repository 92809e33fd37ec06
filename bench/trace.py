"""From a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes an XSpace (``*.xplane.pb``). A device's plane is
named ``/device:TPU:<n>``; the operations it ran are the events of its
``XLA Ops`` line, with start and duration in nanoseconds on the same
clock as the host's events. An op event is named by its whole HLO
instruction (``%fusion.8 = (f32[...]) fusion(...)``); ``op_name`` keeps
the instruction's name (``fusion.8``), and kernel patterns match that.
A Pallas kernel's instruction is named after the jitted function that
calls it and the transform: ``jvp_jit_cell_scan__.2`` is ``lstm_scan``'s
forward kernel, ``transpose_jvp_jit_cell_scan___.1`` its backward.
The harness marks each step's phases on the host with
``jax.profiler.TraceAnnotation`` spans named ``bench.feed`` (batch to the
device and step arguments), ``bench.call`` (dispatch of the compiled
step) and ``bench.sync`` (waiting on ``float(loss)``).

``reduce`` takes plain ``(name, start_ns, end_ns)`` tuples, so a test can
hand it a trace built by hand.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, end ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def op_name(event_name: str) -> str:
    """``%fusion.8 = (f32[2]) fusion(...)`` -> ``fusion.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str):
    """({device plane: [op events]}, [host span events]) of the newest
    trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], sorted, disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _span_at(spans: Sequence[Event], lo: float, hi: float) -> str:
    """Name of the host span that overlaps [lo, hi] the most."""
    best, name = 0.0, "host:unmarked"
    for n, s, e in spans:
        ov = min(e, hi) - max(s, lo)
        if ov > best:
            best, name = ov, n
    return name


def reduce(devices: Dict[str, List[Event]], spans: Sequence[Event],
           kernels: Dict[str, Sequence[str]], top: int = 10) -> dict:
    """Reduce one traced window.

    The window runs from the first harness span's start to the last one's
    end. ``kernels`` maps a kernel to the patterns (regular expressions,
    matched from the start of the op name) of its device ops. Returns
    seconds averaged over the device planes: ``busy_s`` (union of op
    intervals), ``window_s``, ``kernel_s`` per kernel, ``device_ops``
    (the ``top`` op names by time) and ``idle_gaps`` (the ``top``
    longest gaps between device ops, each named by the host span that
    overlaps it most). Op names are grouped without their ``.<n>``
    suffix in ``device_ops``.
    """
    if not spans or not devices:
        raise ValueError("trace holds no harness spans or no device ops")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    pats = {k: [re.compile(p) for p in ps] for k, ps in kernels.items()}
    n = len(devices)
    busy = 0.0
    kernel_s = {k: 0.0 for k in kernels}
    by_op: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for ops in devices.values():
        cover = union(((s, e) for _, s, e in ops), lo, hi)
        busy += sum(e - s for s, e in cover)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            group = re.sub(r"\.\d+$", "", name)
            by_op[group] = by_op.get(group, 0.0) + d
            for k, ps in pats.items():
                if any(p.match(name) for p in ps):
                    kernel_s[k] += d
        edges = [lo] + [x for iv in cover for x in iv] + [hi]
        gaps.extend((a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a)
    gaps.sort(key=lambda g: g[0] - g[1])
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy * 1e-9 / n, "window_s": (hi - lo) * 1e-9,
            "kernel_s": {k: v * 1e-9 / n for k, v in kernel_s.items()},
            "device_ops": [[k, v * 1e-9 / n] for k, v in ops_top],
            "idle_gaps": [[_span_at(spans, a, b), (b - a) * 1e-9]
                          for a, b in gaps[:top]]}
