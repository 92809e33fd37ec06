"""Smoke run of the structured-dropout LSTM training path on a TPU.

    python chip_smoke.py               # one chip: "published", "paper-path"
    python chip_smoke.py --four-chips  # four chips: "data-parallel" only

Every phase trains Zaremba-medium, the paper's PTB language model, at its
published width (vocab 10000, embed and hidden 650, 2 layers, batch 20,
unroll 35) from random weights made from seed 0, through the functions
``repro.launch.train`` uses:

  published      ``repro.launch.train.main`` on the arch's ``spec.full()``
                 config (case III rate 0.5, block 1, at embed/nr/rh/out;
                 scheduled engine, xla impl), which is not Zaremba's own
                 case I recipe; 5 steps, every loss finite.
  paper-path     the fused engine with case III dropout at rate 0.5 and
                 block size 65 on all four sites; the recurrent ("rh")
                 site runs the Pallas scan kernel, the others xla. The
                 compiled step must hold the kernel (``tpu_custom_call``),
                 its step-0 loss and every gradient leaf must agree with
                 the same step on the xla scan, and 5 steps must give
                 finite losses.
  data-parallel  the paper-path step under ``make_sharded_train_step`` on
                 a 4-chip data mesh (5 rows per shard): loss and gradients
                 agree with the one-chip step on the same batch and key,
                 and the compiled step holds an all-reduce.

Everything runs in this one process. The lines before the last are
informational (compile seconds and step times include the host). The last
line of stdout is one JSON object, ``{"ok": true, "device": {...}}``; the
script exits non-zero without it when JAX sees no TPU or a check fails.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "zaremba-medium"
SEED = 0
BATCH, SEQ, STEPS = 20, 35, 5
RATE, BLOCK = 0.5, 65            # 65 divides the hidden width 650
LR = 1e-3                        # launch/train.py's default
# paper-path step 0, Pallas rh scan vs xla rh scan, same plan and key. Both
# run f32 matmuls at the TPU's default precision, but not the same ones.
# The gradient check is per leaf: the worst element error relative to that
# leaf's max magnitude, the recurrent U leaves included, so a dU row scattered
# into the wrong keep-block shows even where the global norm would not.
# On a TPU v5 lite the loss differed by 1.0e-7 and the worst leaf by 1.1e-3
# (U: 1.1e-3); a dU scatter shifted by one keep-block gives about 1.4
# (CPU, interpret mode, smoke width).
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-2
# data-parallel vs one chip: the same math on 5-row shards, psum'd; the same
# per-leaf measure.
DP_RTOL = 1e-3


def _require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX sees no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    print(f"device_kind {devs[0].device_kind!r}, {len(devs)} device(s)")
    return devs


def _check_finite(name, losses):
    print(f"[{name}] losses {losses}")
    if len(losses) != STEPS or not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"[{name}] expected {STEPS} finite losses, "
                             f"got {losses}")


def published():
    """launch/train.py's own entry point on the arch's full config."""
    from repro.launch import train
    log = io.StringIO()
    with contextlib.redirect_stdout(log):
        rc = train.main(["--arch", ARCH, "--steps", str(STEPS),
                         "--batch", str(BATCH), "--seq", str(SEQ),
                         "--seed", str(SEED), "--log-every", "1"])
    print(log.getvalue(), end="")
    if rc != 0:
        raise AssertionError(f"[published] train.main returned {rc}")
    rows = re.findall(r"^step\s+\d+\s+loss\s+(\S+)\s+(\d+) ms$",
                      log.getvalue(), re.M)
    _check_finite("published", [float(loss) for loss, _ in rows])
    ms = [int(m) for _, m in rows]
    print(f"[published] step 0 (compile + run) {ms[0]} ms; steps 1-"
          f"{STEPS - 1} median {statistics.median(ms[1:])} ms")


def leaf_errors(got, want):
    """{leaf path: max |got - want| / max |want|} over two gradient trees."""
    import jax
    import numpy as np
    out = {}
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                 jax.tree_util.tree_flatten_with_path(want)[0]):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        out[jax.tree_util.keystr(path)] = float(
            np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))
    return out


def _report_leaves(phase, errs):
    for name, e in errs.items():
        print(f"[{phase}] grad leaf {name} error {e:.3e} of its max")
    return max(errs.values())


def paper_path_cfg(spec, rh_impl):
    """Zaremba-medium, fused engine, case III at every site; ``rh_impl``
    picks the recurrent scan ("pallas" | "xla")."""
    from repro.core.dropout_plan import DropoutPlan
    from repro.core.sdrop import DropoutSpec

    def case3(impl):
        return DropoutSpec.case("case3", RATE, block_size=BLOCK, impl=impl)

    plan = DropoutPlan({"embed": case3("xla"), "nr": case3("xla"),
                        "rh": case3(rh_impl), "out": case3("xla")})
    return dataclasses.replace(spec.full(), plan=plan, engine="fused")


def _setup():
    """(spec, cfg, rules, params, opt, batch_fn) as launch/train.py builds
    them for the paper-path config."""
    from repro import configs, optim
    from repro.distributed import sharding as shd
    from repro.launch import mesh as mesh_mod
    from repro.launch import steps
    from repro.launch.train import make_batch_fn

    spec = configs.get_arch(ARCH)
    cfg = paper_path_cfg(spec, "pallas")
    mesh = mesh_mod.make_host_mesh()
    rules = shd.rules_for_mesh(mesh)
    init_fn, _, _, _ = steps.param_setup(spec, cfg, mesh, rules, seed=SEED)
    opt = optim.chain(optim.clip_by_global_norm(1.0), optim.adamw(LR))
    batch_fn = make_batch_fn(spec, cfg, BATCH, SEQ, SEED)
    return spec, cfg, rules, init_fn(), opt, batch_fn


def _step_inputs(batch_fn, step):
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(SEED), step)
    return (jax.tree.map(jnp.asarray, batch_fn(step)), jnp.int32(step), key)


def _loss_and_grads(spec, cfg, rules, params, batch, step, key):
    import jax
    from repro.configs import adapters
    lfn = adapters.loss_fn(spec.kind)
    return jax.jit(jax.value_and_grad(
        lambda p: lfn(p, batch, cfg, rules=rules, drop_key=key,
                      step=step)))(params)


def paper_path():
    import jax
    from repro.launch import steps

    spec, cfg, rules, params, opt, batch_fn = _setup()
    batch, step, key = _step_inputs(batch_fn, 0)

    # step 0: the Pallas rh scan against the xla rh scan, checked after the
    # 5 steps below so that one run prints every reading
    l_p, g_p = _loss_and_grads(spec, cfg, rules, params, batch, step, key)
    l_x, g_x = _loss_and_grads(spec, paper_path_cfg(spec, "xla"), rules,
                               params, batch, step, key)
    l_p, l_x = float(l_p), float(l_x)
    d_loss = abs(l_p - l_x) / abs(l_x)
    worst = _report_leaves("paper-path", leaf_errors(g_p, g_x))
    print(f"[paper-path] step 0 loss pallas {l_p!r} xla {l_x!r} (rel "
          f"{d_loss:.3e}, limit {LOSS_RTOL:g}); worst gradient leaf error "
          f"{worst:.3e} of its max (limit {GRAD_RTOL:g})")

    opt_state = opt.init(params)
    train_step = steps.make_train_step(spec, cfg, opt, rules)
    t0 = time.perf_counter()
    compiled = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        params, opt_state, batch, step, key).compile()
    print(f"[paper-path] train step compile {time.perf_counter() - t0:.2f} s")

    losses, ms = [], []
    for s in range(STEPS):
        batch, step, key = _step_inputs(batch_fn, s)
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch, step,
                                           key)
        losses.append(float(loss))
        ms.append((time.perf_counter() - t0) * 1e3)
    print(f"[paper-path] steps 1-{STEPS - 1} median "
          f"{statistics.median(ms[1:]):.1f} ms")
    if not (d_loss <= LOSS_RTOL and worst <= GRAD_RTOL):
        raise AssertionError("[paper-path] Pallas and xla rh scans disagree "
                             "at step 0")
    _check_finite("paper-path", losses)
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("[paper-path] compiled step holds no Pallas "
                             "kernel (tpu_custom_call)")


def data_parallel(devs):
    """The paper-path step data-parallel on 4 chips vs one chip."""
    import jax
    from repro.launch import mesh as mesh_mod
    from repro.launch import steps

    if len(devs) < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 devices, JAX "
                         f"sees {len(devs)}")
    spec, cfg, rules, params, opt, batch_fn = _setup()
    batch, step, key = _step_inputs(batch_fn, 0)
    mesh = mesh_mod.make_data_mesh(4)

    loss_1, grads_1 = _loss_and_grads(spec, cfg, rules, params, batch, step,
                                      key)
    sharded_vg = steps.make_sharded_loss_and_grad(spec, cfg, mesh,
                                                  rules=rules)
    loss_4, grads_4 = jax.jit(sharded_vg)(params, batch, step, key)
    worst = _report_leaves("data-parallel", leaf_errors(grads_4, grads_1))
    d_loss = abs(float(loss_4) - float(loss_1)) / abs(float(loss_1))
    print(f"[data-parallel] loss 4 chips {float(loss_4)!r} 1 chip "
          f"{float(loss_1)!r} (rel {d_loss:.3e}); worst gradient leaf error "
          f"{worst:.3e} of its max; limit {DP_RTOL:g}")
    if not (d_loss <= DP_RTOL and worst <= DP_RTOL):
        raise AssertionError("[data-parallel] 4-chip and 1-chip loss or "
                             "gradients disagree")

    t0 = time.perf_counter()
    compiled = jax.jit(steps.make_sharded_train_step(
        spec, cfg, opt, mesh, rules=rules)).lower(
        params, opt.init(params), batch, step, key).compile()
    print(f"[data-parallel] train step compile "
          f"{time.perf_counter() - t0:.2f} s")
    _, _, loss = compiled(params, opt.init(params), batch, step, key)
    print(f"[data-parallel] train step loss {float(loss)!r}")
    if abs(float(loss) - float(loss_1)) > DP_RTOL * abs(float(loss_1)):
        raise AssertionError("[data-parallel] train step loss disagrees")
    text = compiled.as_text()
    if "all-reduce" not in text:
        raise AssertionError("[data-parallel] compiled step holds no "
                             "all-reduce")
    if "tpu_custom_call" not in text:
        raise AssertionError("[data-parallel] compiled step holds no "
                             "Pallas kernel (tpu_custom_call)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel phase")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    print(f"compile cache {compile_cache.enable_compile_cache()}")
    devs = _require_tpu()
    if args.four_chips:
        data_parallel(devs)
    else:
        published()
        paper_path()
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
