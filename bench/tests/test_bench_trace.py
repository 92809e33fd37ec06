"""The reduction from a profiler trace to the per-layer metrics, on a
trace built by hand: device busy and idle share, kernel time per step,
and the idle gaps named by the host span they fall in."""
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
