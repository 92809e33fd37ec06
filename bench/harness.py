"""One benchmark run of one cell: set-up, the measured window, the check.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json`` with its module ``configs/<config>.py``), the
engine, the dropout plan, the traffic parameters, the kernels it runs and
the limits of its check. Everything here is generic over cells.

The timed path is the program's jitted training step
(``repro.launch.steps.make_train_step``, clip to global norm 1 then
AdamW at 1e-3, as ``repro.launch.train`` builds it), driven as that
loop drives it (``Trainer``). Set-up makes the weights on the device
from the seed, compiles the step for each batch shape of the pool, and
drives the same jitted step with its state through the first
``CHECK_STEPS`` steps, which are checked against the plain reference
after the window (``check.py``). The window then goes on from that state
with the same call and feed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import time
from pathlib import Path
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import dropout_ref, traffic

BENCH = Path(__file__).resolve().parent
CHECK_STEPS = 3
LR, B1, B2, EPS, CLIP = 1e-3, 0.9, 0.999, 1e-8, 1.0   # launch/train.py


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict          # workloads/<cell>.json
    conf: dict          # configs/<config>.json
    mod: object         # configs/<config>.py

    @property
    def sizes(self) -> dict:
        return self.conf["sizes"]

    @property
    def plan(self) -> dict:
        return self.spec["plan"]


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_config_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, *, sizes: Optional[dict] = None,
              traffic_override: Optional[dict] = None) -> Cell:
    """The cell's files, found by its name. ``sizes`` and
    ``traffic_override`` replace entries, for tests at a small size."""
    spec = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    cfg = spec["config"]
    conf = json.loads((BENCH / "configs" / f"{cfg}.json").read_text())
    if sizes:
        conf = {**conf, "sizes": {**conf["sizes"], **sizes}}
    if traffic_override:
        spec = {**spec, "traffic": {**spec["traffic"], **traffic_override}}
    return Cell(name, spec, conf, _module(BENCH / "configs" / f"{cfg}.py"))


def kernels_missing(hlo_text: str, kernels: dict) -> list:
    """The cell's kernels (``kernels[k]["hlo"]``: the op names of its
    Pallas calls) that the compiled step does not hold."""
    calls = [line for line in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return [f"{k}: {op}" for k, v in kernels.items() for op in v["hlo"]
            if not any(f'op_name="jit(train_step)/{op}"' in c for c in calls)]


def root_key(seed: int):
    """A PRNG key from any non-negative seed (both 32-bit halves)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def weight_key(seed: int):
    return jax.random.fold_in(root_key(seed), 0x5EED)


def kept_fn(plan: dict):
    """kept(site, dim, steps=1): rows a matmul needs behind the site's
    dropout. With ``steps`` > 1, the rows kept at one step or more of
    that many per-step draws (expected): the rows of a weight that a scan
    over those steps has to read, and whose gradient it writes."""

    def kept(site, dim, steps=1):
        s = dropout_ref.site_spec(plan, site)
        if s is None or float(s["rate"]) <= 0 or s["case"] != "case3":
            return dim
        bs = dropout_ref.fit_block(int(s.get("block", 1)), dim)
        rows = dropout_ref.kept_blocks(dim, float(s["rate"]), bs) * bs
        return rows if steps == 1 else dim * (1.0 - (1.0 - rows / dim)
                                               ** steps)

    return kept


# -- the program -------------------------------------------------------------


def program_config(cell: Cell):
    """(spec, cfg) of the program for this cell: the arch's published
    config with the cell's sizes, plan and engine."""
    from repro import configs
    from repro.core.dropout_plan import DropoutPlan
    from repro.core.sdrop import DropoutSpec

    spec = configs.get_arch(cell.conf["arch"])
    plan = DropoutPlan({
        site: DropoutSpec.case(s["case"], float(s["rate"]),
                               block_size=int(s.get("block", 1)),
                               impl=s.get("impl", "xla"))
        for site, s in cell.plan.items()})
    cfg = dataclasses.replace(spec.full(), **cell.sizes, plan=plan,
                              engine=cell.spec["engine"])
    return spec, cfg


def program_step(cell: Cell):
    """(step fn, optimizer) as ``repro.launch.train`` builds them; the
    weights' tree must be the program's own."""
    from repro import optim
    from repro.configs import adapters
    from repro.distributed import sharding as shd
    from repro.launch import mesh as mesh_mod
    from repro.launch import steps

    spec, cfg = program_config(cell)
    rules = shd.rules_for_mesh(mesh_mod.make_host_mesh())
    opt = optim.chain(optim.clip_by_global_norm(CLIP), optim.adamw(LR))
    theirs = shd.strip(jax.eval_shape(
        lambda: adapters.init_params(spec.kind, jax.random.PRNGKey(0), cfg)))
    ours = jax.eval_shape(lambda: init_weights(cell, jax.random.PRNGKey(0)))
    if jax.tree.structure(theirs) != jax.tree.structure(ours) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(ours))):
        raise SystemExit(f"{cell.name}: the program's parameter tree is not "
                         f"the one configs/{cell.conf['name']}.py makes")
    return steps.make_train_step(spec, cfg, opt, rules), opt


def init_weights(cell: Cell, key, dtype=jnp.float32):
    return cell.mod.init_weights(key, cell.sizes, cell.conf["init_scale"],
                                 dtype)


# -- the plain reference ------------------------------------------------------


def ref_adam_init(params):
    def zeros():
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    return ((), {"m": zeros(), "v": zeros(), "step": jnp.zeros((), jnp.int32)})


def ref_step(cell: Cell, precision) -> Callable:
    """The reference training step, with the program's signature and the
    layout of its optimizer state: the loss of ``configs/<config>.py``
    under the masks of ``dropout_ref``, its gradient, clipping to global
    norm 1 and AdamW (no weight decay), written out here."""

    def fn(params, opt_state, batch, step, key):
        masks = dropout_ref.Masks(cell.plan, key, step,
                                  jax.tree.leaves(params)[0].dtype)
        loss, g = jax.value_and_grad(
            lambda p: cell.mod.ref_loss(p, batch, masks, precision))(params)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, CLIP / norm), g)
        st = opt_state[1]
        t = st["step"] + 1
        m = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, st["m"], g)
        v = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, st["v"], g)
        params = jax.tree.map(
            lambda p, m, v: p + (-LR * (m / (1 - B1 ** t))
                                 / (jnp.sqrt(v / (1 - B2 ** t)) + EPS)
                                 ).astype(p.dtype), params, m, v)
        return params, ((), {"m": m, "v": v, "step": t}), loss

    return fn


# -- driving a step -------------------------------------------------------------


class Trainer:
    """A jitted step with its state, driven one step at a time as
    ``repro.launch.train``'s loop drives it: the batch to the device leaf
    by leaf, the call with ``jnp.int32(step)`` and ``drop_key =
    fold_in(key, step)``, ``float(loss)``, the step's time against the
    median of the last 50. ``warm`` compiles the shapes of given steps
    ahead of them. With ``annotate`` set each phase is a profiler span
    (``trace.py``)."""

    def __init__(self, fn, params, opt_state, pool, seed: int):
        self.pool, self.key = pool, root_key(seed)
        self.params, self.opt_state = params, opt_state
        self.annotate, self.t_called = False, 0.0
        self.jitted = jax.jit(fn, donate_argnums=(0, 1))
        self.compiled = {}              # batch shape -> compiled step
        self.times = []

    def _args(self, i):
        batch = jax.tree.map(jnp.asarray, self.pool[i % len(self.pool)])
        return batch, jnp.int32(i), jax.random.fold_in(self.key, i)

    def warm(self, steps) -> None:
        """Compile the step for the batch shape of each of ``steps``."""
        for i in steps:
            shape = traffic.shape_of(self.pool[i % len(self.pool)])
            if shape not in self.compiled:
                self.compiled[shape] = self.jitted.lower(
                    self.params, self.opt_state, *self._args(i)).compile()

    def _call(self, args):
        self.params, self.opt_state, loss = self.jitted(
            self.params, self.opt_state, *args)
        self.t_called = time.perf_counter()
        return loss

    def step(self, i: int) -> float:
        t0 = time.perf_counter()
        if not self.annotate:
            loss = float(self._call(self._args(i)))
        else:
            span = jax.profiler.TraceAnnotation
            with span("bench.feed"):
                args = self._args(i)
            with span("bench.call"):
                loss = self._call(args)
            with span("bench.sync"):
                loss = float(loss)
        self.times.append(time.perf_counter() - t0)
        np.median(self.times[-50:])     # the loop's straggler check
        return loss

    def free(self):
        self.params = self.opt_state = self.jitted = self.compiled = None


def _leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in leaves])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


def first_steps(tr: Trainer, cell: Cell, seed: int) -> dict:
    """Drive steps 0..CHECK_STEPS-1 and read what the check compares:
    each step's loss, the per-leaf norms of the first (clipped) gradient
    as AdamW's first moment holds it after step 0, and the per-leaf norms
    of the parameters' change over the steps."""
    losses, grad = [], None
    for i in range(CHECK_STEPS):
        losses.append(tr.step(i))
        if i == 0:
            m = tr.opt_state[1]["m"]
            grad = {k: v / (1 - B1) for k, v in _leaf_norms(m).items()}
    p0 = jax.jit(lambda k: init_weights(
        cell, k, jax.tree.leaves(tr.params)[0].dtype))(weight_key(seed))
    delta = _leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        tr.params, p0))
    return {"losses": losses, "grad": grad, "delta": delta}


def reference_readings(cell: Cell, seed: int, pool, *,
                       dtype=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST) -> dict:
    """The reference's readings of the same first steps."""
    params = jax.jit(lambda k: init_weights(cell, k, dtype))(weight_key(seed))
    tr = Trainer(ref_step(cell, precision), params, ref_adam_init(params),
                 pool, seed)
    out = first_steps(tr, cell, seed)
    tr.free()
    return out


def window(tr: Trainer, start: int, seconds: float, tokens: list,
           on_step: Optional[Callable] = None) -> dict:
    """Drive steps from ``start`` until ``seconds`` have passed; every
    step ends in its loss sync. ``on_step(t_called, t_synced)`` is told
    when each step's dispatch and sync returned. Returns the window's
    counts and times."""
    gc.collect()
    t0 = time.perf_counter()
    times, n_tok, failed, i = [], 0, 0, start
    end = t0
    while end - t0 < seconds:
        s0 = time.perf_counter()
        loss = tr.step(i)
        end = time.perf_counter()
        times.append(end - s0)
        failed += not np.isfinite(loss)
        n_tok += tokens[i % len(tokens)]
        if on_step is not None:
            on_step(tr.t_called, end)
        i += 1
    return {"steps": len(times), "failed": failed, "tokens": n_tok,
            "seconds": end - t0, "step_s": times}


# -- what drives the window ------------------------------------------------------

VARIANTS = ("program", "control", "half_batch", "frozen")


def _half(batch):
    return jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)


def make_trainer(cell: Cell, seed: int, pool, variant: str = "program"):
    """The compiled step the window drives, with its state from the seed.

    ``program`` is the benchmark's. The others exist for the check's own
    readings and tests, never for a benchmark run: ``control`` puts the
    reference, in bfloat16, in the program's place; ``half_batch`` feeds
    the program's step the first half of each batch; ``frozen`` returns
    the state it was given.
    """
    if variant == "control":
        params = jax.jit(lambda k: init_weights(cell, k, jnp.bfloat16))(
            weight_key(seed))
        return Trainer(ref_step(cell, jax.lax.Precision.DEFAULT), params,
                       ref_adam_init(params), pool, seed)
    fn, opt = program_step(cell)
    if variant == "half_batch":
        prog = fn

        def fn(p, o, b, s, k):
            return prog(p, o, _half(b), s, k)
    elif variant == "frozen":
        prog = fn

        def fn(p, o, b, s, k):
            return p, o, prog(p, o, b, s, k)[2]
    elif variant != "program":
        raise ValueError(f"unknown variant {variant!r}; {VARIANTS}")
    params = jax.jit(lambda k: init_weights(cell, k))(weight_key(seed))
    return Trainer(fn, params, jax.jit(opt.init)(params), pool, seed)
