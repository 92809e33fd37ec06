import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST run before any jax import (device count locks on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
(no __future__ import here: the XLA_FLAGS lines above must stay first.)

For each cell this:
  1. builds the production mesh ((16,16) single-pod / (2,16,16) multi-pod),
  2. eval_shapes params/optimizer/state (no allocation — 480B params stay
     abstract),
  3. jits the real step function with NamedShardings and calls
     .lower().compile(),
  4. records memory_analysis() + cost_analysis() + parsed collective bytes
     into a JSON cache (incremental: done cells are skipped on re-run).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
      --shape train_4k --mesh single --variant dense
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.configs.shapes import SHAPES
from repro.distributed import sharding as shd
from repro.launch import hlo_cost
from repro.launch import mesh as mesh_mod
from repro.launch import roofline as rf
from repro.launch import steps


def cell_id(arch, shape, mesh_name, variant):
    return f"{arch}|{shape}|{mesh_name}|{variant}"


def run_cell(spec, shape, mesh, rules, *, use_dropout, dropout="",
             engine="", collect_hlo=False):
    cfg = spec.full()
    cell = steps.build_cell(spec, cfg, shape, mesh, rules,
                            use_dropout=use_dropout, dropout=dropout,
                            engine=engine)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = cell.jitted.lower(*cell.example_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost_raw = compiled.cost_analysis()           # loop bodies counted once
    hlo = compiled.as_text()
    la = hlo_cost.analyze_hlo(hlo)                # loop-aware re-derivation

    n_params = rf.count_params(steps.param_setup(spec, cfg, mesh, rules)[1])
    n_active = rf.active_params(spec, cfg, n_params)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    model_flops = rf.model_flops_for(shape.kind, n_active, tokens)
    chips = mesh.devices.size
    roof = rf.analyze_loop_aware(la, chips=chips, model_flops=model_flops)

    rec = {
        "arch": spec.name, "shape": shape.name, "kind": shape.kind,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": int(chips),
        "status": "ok",
        "params": int(n_params), "active_params": int(n_active),
        "tokens_per_step": int(tokens),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": _mem_dict(mem),
        "cost_raw": {k: float(v) for k, v in (cost_raw or {}).items()
                     if isinstance(v, (int, float))},
        "cost": la.as_dict(),
        "roofline": {
            "t_compute_s": roof.t_compute, "t_memory_s": roof.t_memory,
            "t_collective_s": roof.t_collective,
            "bottleneck": roof.bottleneck,
            "model_flops": roof.model_flops,
            "flops_ratio": roof.flops_ratio,
        },
    }
    if collect_hlo:
        rec["hlo_len"] = len(hlo)
    return rec


def _mem_dict(mem):
    if mem is None:
        return {}
    out = {}
    for f in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            out[f] = int(v)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="sdrop",
                    choices=["sdrop", "dense"],
                    help="train cells: structured dropout on (paper mode) "
                         "or off (dense baseline)")
    ap.add_argument("--dropout", default="",
                    help="dropout-plan override applied to every lowered "
                         "cell (e.g. case3:0.5:bs128)")
    ap.add_argument("--engine", default="",
                    choices=["", "scheduled", "stepwise", "fused"],
                    help="recurrent-engine override applied to every "
                         "lowered cell")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--rules", default="",
                    help="comma list of logical=mesh overrides, e.g. "
                         "expert=model,seq=model")
    args = ap.parse_args()

    archs = (list(configs.ASSIGNED_NAMES) if args.arch == "all"
             else args.arch.split(","))
    shapes = (list(SHAPES) if args.shape == "all" else args.shape.split(","))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    cache = {}
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            cache = json.load(f)

    overrides = {}
    for kv in args.rules.split(","):
        if "=" in kv:
            k, v = kv.split("=")
            overrides[k] = None if v in ("none", "None") else v

    n_ok = n_skip = n_fail = 0
    for arch_name in archs:
        spec = configs.get_arch(arch_name)
        for shape_name in shapes:
            shape = SHAPES[shape_name]
            skip = spec.applicable(shape_name)
            for multi in meshes:
                mesh_name = "2x16x16" if multi else "16x16"
                cid = cell_id(arch_name, shape_name, mesh_name, args.variant)
                if skip:
                    cache[cid] = {"arch": arch_name, "shape": shape_name,
                                  "mesh": mesh_name, "status": "skip",
                                  "reason": skip}
                    n_skip += 1
                    print(f"[skip] {cid}: {skip[:60]}")
                    continue
                if cid in cache and cache[cid].get("status") == "ok":
                    n_ok += 1
                    print(f"[cached] {cid}")
                    continue
                mesh = mesh_mod.make_production_mesh(multi_pod=multi)
                rules = shd.rules_for_mesh(mesh, overrides)
                t0 = time.time()
                try:
                    rec = run_cell(spec, shape, mesh, rules,
                                   use_dropout=(args.variant == "sdrop"),
                                   dropout=args.dropout,
                                   engine=args.engine)
                    rec["variant"] = args.variant
                    cache[cid] = rec
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"[ok] {cid}  compile={rec['compile_s']}s "
                          f"compute={r['t_compute_s']*1e3:.1f}ms "
                          f"mem={r['t_memory_s']*1e3:.1f}ms "
                          f"coll={r['t_collective_s']*1e3:.1f}ms "
                          f"bottleneck={r['bottleneck']} "
                          f"ratio={r['flops_ratio']:.3f}")
                except Exception as e:
                    n_fail += 1
                    cache[cid] = {"arch": arch_name, "shape": shape_name,
                                  "mesh": mesh_name, "status": "fail",
                                  "error": f"{type(e).__name__}: {e}"}
                    print(f"[FAIL] {cid} ({time.time()-t0:.0f}s): "
                          f"{type(e).__name__}: {str(e)[:200]}")
                    traceback.print_exc(limit=3)
                with open(args.out, "w") as f:
                    json.dump(cache, f, indent=1)

    with open(args.out, "w") as f:       # final dump (covers skip records)
        json.dump(cache, f, indent=1)
    print(f"\ndone: ok={n_ok} skip={n_skip} fail={n_fail} -> {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
