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

Device time by layer (``layer_s``) reads the op_name metadata that the
program's ``jax.named_scope``s leave on each HLO instruction. Each op
event lies inside an event of the device's ``XLA Modules`` line, which
names the compiled module that ran it; the instruction is looked up in
that module's compiled HLO text (``Hlo``), where the first name under the
jitted step, without its ``jvp(`` and ``transpose(`` wrappers, is the
layer (``scope_of``). An op with no such name takes the layer of its
first consumer in the module (an async ``copy-done``, a layout ``copy``,
an op named after a parameter), or where only the module's result reads
it, its producer's; what is left is ``UNATTRIBUTED``. An op
counts its self time: an event that encloses others (a ``while`` and its
body) counts only the part they do not cover.

``reduce`` takes plain ``(name, start_ns, end_ns)`` tuples, so a test can
hand it a trace built by hand.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, end ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
UNATTRIBUTED = "unattributed"


def op_name(event_name: str) -> str:
    """``%fusion.8 = (f32[2]) fusion(...)`` -> ``fusion.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str):
    """({device plane: [op events]}, [host span events], {device plane:
    [module events]}) of the newest trace under ``trace_dir``. An op
    event keeps its whole name; ``op_name`` shortens it."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            mods = modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    (ops if line.name == OPS_LINE else mods).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans, modules


def scope_of(op_name: str, scopes: Iterable[str]) -> Optional[str]:
    """The layer that an HLO op's ``op_name`` names: the first name under
    the jitted step (or under its ``shard_map``), with its ``jvp(`` and
    ``transpose(`` wrappers taken off, where that name is one of
    ``scopes``; else None. ``jit(train_step)/transpose(jvp(loss))/...``
    is ``loss``; ``jit(train_step)/jvp(jit(cell_scan))/...`` is
    ``jit(cell_scan)``, the nested jit that runs a recurrence."""
    parts = op_name.split("/")
    if parts[1:2] == ["shard_map"]:
        parts = parts[1:]
    if len(parts) < 2 or not parts[0].startswith(("jit(", "shard_map")):
        return None
    head = parts[1]
    while head.startswith(("transpose(", "jvp(")) and head.endswith(")"):
        head = head[head.index("(") + 1:-1]
    return head if head in scopes else None


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")


def _result_shape(rhs: str) -> Optional[str]:
    """First array shape of an instruction's result, without its layout:
    ``f32[20,650]`` of ``f32[20,650]{1,0} fusion(...)``."""
    m = _SHAPE.search(rhs)
    return m.group(0) if m else None


class Hlo:
    """One compiled module (``compiled.as_text()``): for each instruction,
    the layer it counts for among ``scopes``, by its op_name, else by its
    first consumer's, else by its producer's (``layer``)."""

    def __init__(self, text: str, scopes: Iterable[str]):
        first = text.split("\n", 1)[0]
        self.name = (first.split()[1].rstrip(",")
                     if first.startswith("HloModule") else "")
        self.shape: Dict[str, Optional[str]] = {}
        self._own: Dict[str, Optional[str]] = {}
        self._next: Dict[str, str] = {}   # first consumer, else the caller
        self._prev: Dict[str, str] = {}   # first operand
        scopes = frozenset(scopes)
        comps: Dict[str, List[Tuple[str, List[str]]]] = {}
        body: Optional[List[Tuple[str, List[str]]]] = None
        for line in text.splitlines():
            m = _INSTRUCTION.match(line)
            if m and body is not None:
                name, rhs = m.groups()
                op = _OP_NAME.search(rhs)
                self._own[name] = scope_of(op.group(1), scopes) if op else None
                self.shape[name] = _result_shape(rhs)
                body.append((name, _REF.findall(rhs.split(", metadata=")[0])))
                continue
            m = _COMPUTATION.match(line)
            if m and not line.startswith((" ", "HloModule")):
                body = comps.setdefault(m.group(1), [])
        caller: Dict[str, str] = {}
        for instrs in comps.values():
            for name, refs in instrs:
                for r in refs:
                    if r in comps:
                        caller.setdefault(r, name)
        for comp, instrs in comps.items():
            names = {n for n, _ in instrs}
            for name, refs in instrs:
                for r in refs:
                    if r in names:
                        self._next.setdefault(r, name)
                        self._prev.setdefault(name, r)
            for name, _ in instrs:
                if name not in self._next and comp in caller:
                    self._next[name] = caller[comp]
        self._layer: Dict[str, Optional[str]] = {}

    def _follow(self, name: str, step: Dict[str, str]) -> Optional[str]:
        seen = set()
        while name is not None and name not in seen:
            seen.add(name)
            if self._own.get(name) is not None:
                return self._own[name]
            name = step.get(name)
        return None

    def layer(self, instr: str) -> Optional[str]:
        """``instr``'s layer: its own scope, else the first consumer's
        (through unscoped consumers and, from a computation's root, the
        instruction that calls it), else, for an op that only the
        module's result reads (an async copy of an updated parameter out
        to its output buffer), its first operand's; None where neither
        chain finds one."""
        if instr not in self._layer:
            self._layer[instr] = (self._follow(instr, self._next)
                                  or self._follow(instr, self._prev))
        return self._layer[instr]


def _score(mod: Hlo, ops: Sequence[Tuple[str, str]]) -> int:
    """How many of a module event's ops (instruction, result shape) the
    module holds, shapes compared where the event names one."""
    return sum(1 for n, sh in ops if n in mod.shape
               and (sh is None or mod.shape[n] == sh))


def self_times(ops: Sequence[Event], lo: float,
               hi: float) -> List[Tuple[str, float, float]]:
    """(name, start, self ns) of each op event: the part of [lo, hi] in
    which it is the innermost event running, the one started last (of
    two that start together, the shorter). An event that encloses others
    keeps what they leave uncovered, and the self times add up to the
    union of the events."""
    evs = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [0.0] * len(evs)
    running: List[Tuple[int, float]] = []     # (-index, end) heap
    t = lo

    def run_until(t_next):
        nonlocal t
        while running and t < t_next:
            i, end = -running[0][0], running[0][1]
            if end > t:
                u = min(end, t_next)
                own[i] += max(min(u, hi) - max(t, lo), 0.0)
                t = u
            if end <= t:
                heapq.heappop(running)

    for i, (_, s, e) in enumerate(evs):
        run_until(s)
        t = max(t, s)
        heapq.heappush(running, (-i, e))
    run_until(hi)
    return [(n, s, own[i]) for i, (n, s, _) in enumerate(evs)]


def layer_times(devices: Dict[str, List[Event]],
                modules: Dict[str, List[Event]], hlo: Sequence[Hlo],
                lo: float, hi: float) -> Dict[str, float]:
    """Self ns of the ops in [lo, hi] by layer, summed over the planes.
    Each module event is joined with the compiled module of ``hlo`` of the
    same name that holds the most of its ops (``_score``): modules of one
    program at several shapes share instruction names, not shapes."""
    out: Dict[str, float] = {}
    for plane, ops in devices.items():
        mods = sorted(modules.get(plane, ()), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        timed = self_times(ops, lo, hi)
        by_mod: Dict[int, List[Tuple[str, float]]] = {}
        for name, s, d in timed:
            k = bisect.bisect_right(starts, s) - 1
            k = k if k >= 0 and s < mods[k][2] else -1
            by_mod.setdefault(k, []).append((name, d))
        chosen: Dict[tuple, Optional[Hlo]] = {}
        for k, group in by_mod.items():
            mod = None
            if k >= 0:
                seen = [(op_name(n), _event_shape(n)) for n, _ in group[:256]]
                key = (mods[k][0].split("(")[0], tuple(seen))
                if key not in chosen:
                    named = [h for h in hlo if h.name == key[0]]
                    best = max(named, key=lambda h: _score(h, seen),
                               default=None)
                    chosen[key] = (best if best is not None
                                   and _score(best, seen) else None)
                mod = chosen[key]
            for name, d in group:
                lay = mod.layer(op_name(name)) if mod else None
                lay = lay or UNATTRIBUTED
                out[lay] = out.get(lay, 0.0) + d
    return out


def _event_shape(event_name: str) -> Optional[str]:
    """The result shape an op event's whole name gives, or None."""
    return _result_shape(event_name.split(" = ", 1)[1]) \
        if " = " in event_name else None


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
           kernels: Dict[str, Sequence[str]], top: int = 10, *,
           modules: Optional[Dict[str, List[Event]]] = None,
           hlo: Sequence[Hlo] = ()) -> dict:
    """Reduce one traced window.

    The window runs from the first harness span's start to the last one's
    end. ``kernels`` maps a kernel to the patterns (regular expressions,
    matched from the start of the op name) of its device ops. Returns
    seconds averaged over the device planes: ``busy_s`` (union of op
    intervals), ``window_s``, ``kernel_s`` per kernel, ``device_ops``
    (the ``top`` op names by time), ``idle_gaps`` (the ``top`` longest
    gaps between device ops, each named by the host span that overlaps it
    most) and ``layer_s``, self time per layer (``layer_times``) of the
    ops joined through ``modules`` (module events per plane) with the
    compiled modules ``hlo``; without them every op is
    ``UNATTRIBUTED``. Op names are grouped without their ``.<n>`` suffix
    in ``device_ops``.
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
        for event, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            name = op_name(event)
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
    layers = layer_times(devices, modules or {}, hlo, lo, hi)
    return {"busy_s": busy * 1e-9 / n, "window_s": (hi - lo) * 1e-9,
            "kernel_s": {k: v * 1e-9 / n for k, v in kernel_s.items()},
            "device_ops": [[k, v * 1e-9 / n] for k, v in ops_top],
            "idle_gaps": [[_span_at(spans, a, b), (b - a) * 1e-9]
                          for a, b in gaps[:top]],
            "layer_s": {k: v * 1e-9 / n for k, v in layers.items()}}
