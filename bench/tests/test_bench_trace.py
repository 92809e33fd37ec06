"""The reduction from a profiler trace to the per-layer metrics, on a
trace built by hand: device busy and idle share, kernel time per step,
the idle gaps named by the host span they fall in, and device self time
by layer, joined with a compiled module written by hand."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import readers, trace  # noqa: E402

MS = 1e6   # ns


def _fixture():
    """Two steps of 10 ms on one chip. Host: feed 0-2, call 2-3, sync
    3-10 ms, then again from 10 ms. Device: a kernel's forward 3-5 ms,
    a fusion 5-6 ms, the kernel's backward 6-9 ms (overlapping a copy
    8-8.5 ms); the second step the same, 10 ms later."""
    spans, ops = [], []
    for k in range(2):
        t = 10 * k * MS
        spans += [("bench.feed", t, t + 2 * MS),
                  ("bench.call", t + 2 * MS, t + 3 * MS),
                  ("bench.sync", t + 3 * MS, t + 10 * MS)]
        ops += [(f"jvp_jit_cell_scan__.{k}", t + 3 * MS, t + 5 * MS),
                (f"fusion.{k}", t + 5 * MS, t + 6 * MS),
                (f"transpose_jvp_jit_cell_scan___.{k}", t + 6 * MS,
                 t + 9 * MS),
                (f"copy.{k}", t + 8 * MS, t + 8.5 * MS)]
    return {"/device:TPU:0": ops}, spans


KERNELS = {"lstm_scan": ["jvp_jit_cell_scan__",
                         "transpose_jvp_jit_cell_scan___"]}


def _reading(red, steps=2):
    work = {"lstm_scan": (197e12 * 1e-3, 0.0)}    # 1 ms at the FLOP peak
    return readers.Reading(red, steps, [3.0, 3.0], 197e12 * 2e-3, work,
                           {"flops": 197e12, "hbm_bytes_per_s": 819e9})


def test_busy_and_window():
    red = trace.reduce(*_fixture(), KERNELS)
    assert red["window_s"] == pytest.approx(20e-3)
    assert red["busy_s"] == pytest.approx(12e-3)   # 6 ms a step, copy inside


@pytest.mark.parametrize("metric, want", [
    ("device_idle_pct", 40.0),       # 8 of 20 ms
    ("step_device_ms", 6.0),
    ("lstm_scan_ms", 5.0),           # 2 + 3 ms a step
    ("lstm_scan_roofline", 20.0),    # 1 ms least time over 5 ms
    ("mfu", 20.0),                   # 2 ms of peak work a 10 ms step
    ("host_ms_per_step", 3.0),
])
def test_metric_readers(metric, want):
    import importlib
    red = trace.reduce(*_fixture(), KERNELS)
    mod = importlib.import_module(f"bench.metrics.{metric}")
    assert mod.read(_reading(red)) == pytest.approx(want)


def test_reader_finds_nothing():
    """A kernel that did not run gives no reading, not a 0."""
    import importlib
    red = trace.reduce(*_fixture(), {"lstm_scan": ["jvp_jit__decoder"]})
    for metric in ("lstm_scan_ms", "lstm_scan_roofline"):
        mod = importlib.import_module(f"bench.metrics.{metric}")
        assert mod.read(_reading(red)) is None


def test_idle_gaps_named_by_host_span():
    red = trace.reduce(*_fixture(), KERNELS)
    gaps = red["idle_gaps"]
    # 9-13 ms: sync 1 ms, feed 2 ms, call 1 ms -> feed; 0-3 ms: feed;
    # 19-20 ms: the last sync. Longest first.
    assert [g[0] for g in gaps] == ["bench.feed", "bench.feed", "bench.sync"]
    assert [g[1] for g in gaps] == pytest.approx([4e-3, 3e-3, 1e-3])
    assert sum(g[1] for g in gaps) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_device_ops_grouped_and_sorted():
    red = trace.reduce(*_fixture(), KERNELS)
    names = [n for n, _ in red["device_ops"]]
    assert names[0] == "transpose_jvp_jit_cell_scan___"
    assert dict(red["device_ops"])["copy"] == pytest.approx(1e-3)


def test_union_clips_and_merges():
    assert trace.union([(5, 7), (1, 3), (2, 4), (8, 20)], 0, 10) == [
        (1, 4), (5, 7), (8, 10)]


def test_op_name():
    assert trace.op_name("%fusion.8 = (f32[2]{0}) fusion(%a), kind=kLoop"
                         ) == "fusion.8"


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({}, _fixture()[1], KERNELS)


# -- device time by layer ------------------------------------------------------

SCOPES = ("embed", "phase_a", "loss", "adamw", "jit(cell_scan)")


def _hlo(n, embed_fusion="embed"):
    """A compiled step at batch ``n``: an async copy of a parameter that
    feeds the embedding; a scan (``while``) whose body holds a Phase A
    fusion and an unscoped copy; an AdamW fusion whose copy out to the
    output only the module's result reads; a copy of a parameter that
    only the result reads. ``embed_fusion`` names the scope of
    ``fusion.1``, to tell two modules apart that share its name."""
    return f"""HloModule jit_train_step, is_scheduled=true

%fused (p0: f32[{n}]) -> f32[{n}] {{
  %p0 = f32[{n}]{{0}} parameter(0)
  ROOT %neg = f32[{n}]{{0}} negate(%p0), metadata={{op_name="jit(train_step)/jvp(loss)/neg"}}
}}

%cond (c: (s32[], f32[{n}])) -> pred[] {{
  %c = (s32[], f32[{n}]{{0}}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%c), index=0
  %k = s32[] constant(4)
  ROOT %lt = pred[] compare(%gte.0, %k), direction=LT
}}

%body (b: (s32[], f32[{n}])) -> (s32[], f32[{n}]) {{
  %b = (s32[], f32[{n}]{{0}}) parameter(0)
  %gte.1 = s32[] get-tuple-element(%b), index=0
  %gte.2 = f32[{n}]{{0}} get-tuple-element(%b), index=1
  %fusion.3 = f32[{n}]{{0}} fusion(f32[{n}]{{0}} %gte.2), kind=kLoop, calls=%fused, metadata={{op_name="jit(train_step)/transpose(jvp(phase_a))/mul" source_file="x.py" source_line=3}}
  %copy.4 = f32[{n}]{{0}} copy(f32[{n}]{{0}} %fusion.3)
  ROOT %tuple.1 = (s32[], f32[{n}]{{0}}) tuple(%gte.1, %copy.4)
}}

ENTRY %main.9 (a: f32[{n}], i: s32[]) -> (f32[{n}], f32[{n}]) {{
  %a = f32[{n}]{{0}} parameter(0), metadata={{op_name="params[\\'embed\\']"}}
  %i = s32[] parameter(1), metadata={{op_name="step"}}
  %copy-start.1 = (f32[{n}]{{0}}, f32[{n}]{{0}}, u32[]) copy-start(f32[{n}]{{0}} %a)
  %copy-done.1 = f32[{n}]{{0}} copy-done((f32[{n}]{{0}}, f32[{n}]{{0}}, u32[]) %copy-start.1)
  %fusion.1 = f32[{n}]{{0}} fusion(f32[{n}]{{0}} %copy-done.1), kind=kLoop, calls=%fused, metadata={{op_name="jit(train_step)/jvp({embed_fusion})/gather"}}
  %tuple.0 = (s32[], f32[{n}]{{0}}) tuple(%i, %fusion.1)
  %while.2 = (s32[], f32[{n}]{{0}}) while(%tuple.0), condition=%cond, body=%body, metadata={{op_name="jit(train_step)/transpose(jvp(jit(cell_scan)))/while"}}
  %gte.3 = f32[{n}]{{0}} get-tuple-element(%while.2), index=1
  %fusion.9 = f32[{n}]{{0}} fusion(f32[{n}]{{0}} %gte.3), kind=kLoop, calls=%fused, metadata={{op_name="jit(train_step)/adamw/add"}}
  %copy.7 = f32[{n}]{{0}} copy(f32[{n}]{{0}} %fusion.9)
  %copy.8 = f32[{n}]{{0}} copy(f32[{n}]{{0}} %a)
  ROOT %tuple.9 = (f32[{n}]{{0}}, f32[{n}]{{0}}) tuple(%copy.8, %copy.7)
}}
"""


def _op(hlo, name, s, e):
    """An op event named by its whole instruction, as the trace has it."""
    line = next(x for x in hlo.splitlines()
                if x.lstrip().startswith(("%" + name + " ",
                                          "ROOT %" + name + " ")))
    return (line.strip().split(", metadata=")[0].removeprefix("ROOT "),
            s, e)


def _step(hlo, t):
    """One step's ops from ``t`` ms: copy-done 0-1, fusion.1 1-2, the
    while 2-7 holding fusion.3 3-5 and copy.4 5-6, fusion.9 7-8, copy.7
    8-9, copy.8 9-9.5; its module event 0-9.8."""
    at = [("copy-done.1", 0, 1), ("fusion.1", 1, 2), ("while.2", 2, 7),
          ("fusion.3", 3, 5), ("copy.4", 5, 6), ("fusion.9", 7, 8),
          ("copy.7", 8, 9), ("copy.8", 9, 9.5)]
    return ([_op(hlo, n, (t + s) * MS, (t + e) * MS) for n, s, e in at],
            ("jit_train_step(7)", t * MS, (t + 9.8) * MS))


def _layered(texts, steps):
    """Reduce a trace of ``steps`` (module text, start ms) with the
    compiled modules ``texts``."""
    ops, mods, spans = [], [], []
    for text, t in steps:
        o, m = _step(text, t)
        ops += o
        mods.append(m)
        spans.append(("bench.call", t * MS, (t + 10) * MS))
    return trace.reduce({"/device:TPU:0": ops}, spans, {}, modules={
        "/device:TPU:0": mods}, hlo=[trace.Hlo(x, SCOPES) for x in texts])


def test_while_counts_its_own_time():
    """The while's 5 ms less its body's 3 ms are the recurrence's; the
    body's Phase A fusion keeps its own 2 ms."""
    red = _layered([_hlo(4)], [(_hlo(4), 0)])
    lay = red["layer_s"]
    assert lay["phase_a"] == pytest.approx(2e-3)
    # while 2 ms, and copy.4 whose consumer is the body's root: the while's
    assert lay["jit(cell_scan)"] == pytest.approx(3e-3)
    assert sum(lay.values()) == pytest.approx(red["busy_s"])


def test_unscoped_ops_take_their_consumers_layer():
    """copy-done.1 (no op_name) feeds fusion.1 (embed): 2 ms of embed.
    copy.7, read only by the module's result, takes its producer's
    layer (AdamW's); copy.8, whose producer is a parameter, is
    unattributed."""
    lay = _layered([_hlo(4)], [(_hlo(4), 0)])["layer_s"]
    assert lay["embed"] == pytest.approx(2e-3)
    assert lay["adamw"] == pytest.approx(2e-3)
    assert lay[trace.UNATTRIBUTED] == pytest.approx(0.5e-3)


def test_modules_of_one_name_kept_apart():
    """Two compiled shapes of one step (NMT's buckets) share instruction
    names; each module event is read with the module whose shapes its
    ops have. At batch 8, fusion.1 is the loss's."""
    a, b = _hlo(4), _hlo(8, embed_fusion="loss")
    for texts in ([a, b], [b, a]):
        lay = _layered(texts, [(a, 0), (b, 10), (a, 20)])["layer_s"]
        assert lay["embed"] == pytest.approx(4e-3)      # 2 ms, twice
        assert lay["loss"] == pytest.approx(2e-3)


def test_ops_outside_known_modules_unattributed():
    """Without compiled modules, or outside any module event, every op
    is unattributed, and the other outputs do not change."""
    a = _hlo(4)
    red = _layered([a], [(a, 0)])
    bare = trace.reduce({"/device:TPU:0": _step(a, 0)[0]},
                        [("bench.call", 0, 10 * MS)], {})
    assert bare["layer_s"] == {trace.UNATTRIBUTED: pytest.approx(9.5e-3)}
    assert {k: v for k, v in red.items() if k != "layer_s"} == {
        k: v for k, v in bare.items() if k != "layer_s"}


def test_ops_of_other_modules_unattributed():
    """Ops that run inside a module event of another name (an eager
    ``fold_in`` of the loop) stay unattributed, even where the step's
    module has an instruction of the same name and shape."""
    a = _hlo(4)
    ops, (_, s, e) = _step(a, 0)
    red = trace.reduce({"/device:TPU:0": ops}, [("bench.call", 0, 10 * MS)],
                       {}, modules={"/device:TPU:0": [("jit_fold_in(3)", s,
                                                       e)]},
                       hlo=[trace.Hlo(a, SCOPES)])
    assert red["layer_s"] == {trace.UNATTRIBUTED: pytest.approx(9.5e-3)}


def test_existing_outputs_unchanged():
    """``reduce`` adds ``layer_s`` and nothing else to what it returned
    before device time was read by layer."""
    red = trace.reduce(*_fixture(), KERNELS)
    assert set(red) == {"busy_s", "window_s", "kernel_s", "device_ops",
                        "idle_gaps", "layer_s"}
    assert red["kernel_s"] == {"lstm_scan": pytest.approx(10e-3)}
    assert red["layer_s"] == {trace.UNATTRIBUTED: pytest.approx(12e-3)}


@pytest.mark.parametrize("op, want", [
    ("jit(train_step)/transpose(jvp(loss))/log_softmax", "loss"),
    ("jit(train_step)/jvp(jit(cell_scan))/while/body/dot", "jit(cell_scan)"),
    ("jit(train_step)/shard_map/adamw/add", "adamw"),
    ("jit(train_step)/jvp()/jit(_threefry_fold_in)/xor", None),
    ("jit(train_step)/jvp(embed)", "embed"),
    ("jit(train_step)/grad_psum/psum", None),       # not read by SCOPES
    ("params['embed']", None),
    ("embed/gather", None),
])
def test_scope_of(op, want):
    assert trace.scope_of(op, SCOPES) == want


@pytest.mark.parametrize("metric, scopes, want", [
    ("embed_ms", ["embed"], 2.0),                # ms a step, of 2 steps
    ("phase_a_ms", ["phase_a"], 2.0),
    ("recurrence_ms", ["jit(cell_scan)"], 3.0),
    ("optimizer_ms", ["adamw"], 2.0),
    ("unattributed_ms", [trace.UNATTRIBUTED], 0.5),
    ("attention_ms", [], None),                   # the cell has none
])
def test_layer_readers(metric, scopes, want):
    import importlib
    a = _hlo(4)
    red = _layered([a], [(a, 0), (a, 10)])
    mod = importlib.import_module(f"bench.metrics.{metric}")
    assert all(s in red["layer_s"] for s in scopes)
    got = mod.read(_reading(red))
    assert got == (pytest.approx(want) if want is not None else None)


def test_layer_roofline():
    red = _layered([_hlo(4)], [(_hlo(4), 0)])
    r = _reading(red, steps=1)
    r.work = {"phase_a": (197e12 * 1e-3, 0.0)}    # 1 ms at the FLOP peak
    assert readers.layer_roofline_pct(r, "phase_a") == pytest.approx(50.0)
    assert readers.layer_roofline_pct(r, "embed") is None


def test_self_times_add_up_to_the_union():
    """Nested, chained and partly overlapping events, some across the
    window's edges: their self times add up to the union's length."""
    import random
    rng = random.Random(5)
    ops = []
    for _ in range(300):
        s = rng.uniform(-5, 105)
        ops.append((f"op.{len(ops)}", s, s + rng.choice([0.1, 1, 7, 30])))
    timed = trace.self_times(ops, 0, 100)
    assert all(d >= -1e-9 for _, _, d in timed)
    assert sum(d for _, _, d in timed) == pytest.approx(
        sum(e - s for s, e in trace.union(((s, e) for _, s, e in ops),
                                          0, 100)))
