"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's weights and batches from the seed, compiles the
program's training step once and drives it through its first steps,
which are checked afterwards. The window then drives the same step for
``--seconds``. With ``--trace 0`` the line holds the cell's end-to-end
metrics; with ``--trace 1`` the first seconds of the window are traced
by the profiler and the line holds its per-layer metrics. After the
window the first steps are compared with the plain reference, and the
numbers compared are printed beside their limits, last on stderr and
last in the line. The run needs a TPU: on any other platform it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

TRACE_SECONDS = 3.0     # traced part of a --trace 1 window


def _manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _chips(manifest, name):
    return next((w["chips"] for w in manifest["workloads"]
                 if w["name"] == name), 1)


def _applies(metric, name):
    return "workloads" not in metric or name in metric["workloads"]


def _end_to_end(manifest, name, win, setup_s):
    values = {
        "tokens_per_s": lambda: win["tokens"] / win["seconds"],
        "step_ms_p95": lambda: 1e3 * float(np.percentile(win["step_s"], 95)),
        "setup_s": lambda: setup_s,
    }
    return {m["name"]: {"value": values[m["name"]](), "unit": m["unit"]}
            for m in manifest["end_to_end"] if _applies(m, name)}


def _scopes(manifest):
    """The layer names that the manifest's per-layer metrics read."""
    return {s for m in manifest["per_layer"] for s in getattr(
        importlib.import_module(f"bench.metrics.{m['name']}"), "SCOPES", ())}


def _per_layer(manifest, name, reading):
    out = {}
    for m in manifest["per_layer"]:
        if not _applies(m, name):
            continue
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        v = reader.read(reading)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


class _Tracer:
    """Traces the first ``TRACE_SECONDS`` of the window and keeps, for
    each step traced, the host time from the previous loss sync to the
    return of its dispatch."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.host_ms = []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # Python calls: costly, unread
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True
        self.t0 = self.last = time.perf_counter()

    def step(self, t_called, t_synced):
        if not self.on:
            return
        self.host_ms.append(1e3 * (t_called - self.last))
        self.last = t_synced
        if t_synced - self.t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self):
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def close(self):
        self.stop()
        shutil.rmtree(self.dir, ignore_errors=True)


def _traced_metrics(manifest, name, cell, pool, start, tracer, device,
                    peak, hlo):
    """Per-layer metrics of the steps traced from step ``start``, and the
    breakdown. ``hlo``: the text of each compiled step the window ran."""
    from bench import harness, readers, trace

    tracer.stop()
    devices, spans, modules = trace.load(tracer.dir)
    scopes = _scopes(manifest)
    red = trace.reduce(devices, spans, {k: v["trace"] for k, v in
                                        cell.spec["kernels"].items()},
                       modules=modules,
                       hlo=[trace.Hlo(t, scopes) for t in hlo])
    steps = len(tracer.host_ms)
    kept = harness.kept_fn(cell.plan)
    batches = [pool[(start + i) % len(pool)]
               for i in range(steps)]
    flops = sum(cell.mod.step_flops(cell.sizes, kept, b)
                for b in batches) / steps
    work = {}
    for b in batches:
        for k, (f, n) in cell.mod.kernel_work(cell.sizes, kept, b).items():
            f0, n0 = work.get(k, (0.0, 0.0))
            work[k] = (f0 + f / steps, n0 + n / steps)
    reading = readers.Reading(red, steps, tracer.host_ms, flops, work, peak)
    device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    for k in cell.spec["kernels"]:
        if k in work:
            print(f"{k}: {readers.roofline_bound(reading, k)}-bound by its "
                  f"required work", file=sys.stderr)
    return (_per_layer(manifest, name, reading),
            {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]})


def run(args, *, require_tpu: bool = True, variant: str = "program",
        **overrides) -> dict:
    """Set-up, window and check of one cell; returns the result line.
    Tests pass ``require_tpu=False``, a ``variant`` of
    ``harness.make_trainer`` and ``load_cell``'s small-size overrides."""
    from bench import check, harness, peaks, traffic

    manifest = _manifest()
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devs) < _chips(manifest, args.workload)):
        raise SystemExit(f"bench: needs {_chips(manifest, args.workload)} "
                         f"TPU chip(s); JAX sees {len(devs)} "
                         f"{dev.platform!r} device(s). Nothing was run.")
    peak = peaks.peak(dev.device_kind) if require_tpu else None
    cell = harness.load_cell(args.workload, **overrides)
    pool = traffic.make_pool(cell.spec["traffic"], cell.sizes, args.seed)
    tokens = [cell.mod.loss_tokens(b) for b in pool]
    tr = harness.make_trainer(cell, args.seed, pool, variant)
    tr.warm(range(len(pool)))
    # off the TPU (tests) Pallas kernels are interpreted: none to look for
    missing = sorted({m for c in tr.compiled.values()
                      for m in harness.kernels_missing(
                          c.as_text(), cell.spec["kernels"]
                          if dev.platform == "tpu" else {})})
    prog = harness.first_steps(tr, cell, args.seed)
    start = max(harness.CHECK_STEPS, len(tr.compiled))
    for i in range(harness.CHECK_STEPS, start):   # each shape runs once
        tr.step(i)

    setup_s = time.perf_counter() - T_START
    tr.annotate = bool(args.trace)
    tracer = _Tracer() if args.trace else None
    try:
        win = harness.window(tr, start, args.seconds, tokens,
                             tracer.step if tracer else None)
        stats = dev.memory_stats() or {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs),
                  "memory_peak_bytes": int(stats.get("peak_bytes_in_use",
                                                     0))}
        hlo = [c.as_text() for c in tr.compiled.values()] if tracer else []
        tr.free()
        del tr
        breakdown = None
        if tracer:
            metrics, breakdown = _traced_metrics(
                manifest, args.workload, cell, pool, start, tracer, device,
                peak, hlo)
        else:
            metrics = _end_to_end(manifest, args.workload, win, setup_s)
    finally:
        if tracer:
            tracer.close()

    t_ref = time.perf_counter()
    ref = harness.reference_readings(cell, args.seed, pool)
    print(f"reference {time.perf_counter() - t_ref:.1f} s after the window",
          file=sys.stderr)
    numbers = check.compare(prog, ref)
    correct, checks = check.verdict(numbers, cell.spec["limits"])
    checks["kernels_missing"] = {"value": len(missing), "limit": 0}
    correct &= not missing
    result = {"correct": bool(correct), "attempted": win["steps"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k in missing:
        print(f"missing kernel {k}", file=sys.stderr)
    for name, (value, where) in numbers.items():
        print(f"{name} worst at {where}", file=sys.stderr)
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    result = run(args)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    raise SystemExit(main())
