"""Readings that the limits of a cell's check are set from (on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --faults 11,12,13 [--out calibrate.jsonl]

For every seed, in one process: the program's readings of its first
steps against the reference's (``check.compare``), the same numbers as a
benchmark run compares, at the cell's own size. For each seed of
``--faults``, the same numbers of the control (the reference in bfloat16
put in the program's place) and of the program fed half of each batch.
A step that returns its state unchanged reads 1 on ``grad_gap`` and
``update_gap`` by their definition, and is not run. Prints one JSON line
per reading, then the lower reading of each number (the largest over the
program's seeds) and the upper ones (the smallest of each variant). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import check, harness, traffic

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: needs a TPU")
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [int(s) for s in args.faults.split(",") if s]
    out = open(args.out, "a") if args.out else None
    rows = []
    for seed in dict.fromkeys(seeds + faults):
        pool = traffic.make_pool(cell.spec["traffic"], cell.sizes, seed)
        ref = harness.reference_readings(cell, seed, pool)
        variants = (["program"] if seed in seeds else []) + (
            ["control", "half_batch"] if seed in faults else [])
        for variant in variants:
            tr = harness.make_trainer(cell, seed, pool, variant)
            got = harness.first_steps(tr, cell, seed)
            tr.free()
            nums = check.compare(got, ref)
            row = {"cell": cell.name, "variant": variant, "seed": seed,
                   **{k: v[0] for k, v in nums.items()},
                   "worst": {k: v[1] for k, v in nums.items()},
                   "losses": got["losses"], "ref_losses": ref["losses"]}
            rows.append(row)
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                print(line, file=out, flush=True)
    summary = {"cell": cell.name, "lower": {
        k: max(r[k] for r in rows if r["variant"] == "program")
        for k in check.NAMES}}
    for variant in ("control", "half_batch"):
        vals = [r for r in rows if r["variant"] == variant]
        if vals:
            summary[variant] = {k: min(r[k] for r in vals)
                                for k in check.NAMES}
    print(json.dumps(summary), flush=True)
    if out:
        print(json.dumps(summary), file=out)
        out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
