"""Training driver: --arch selection, fault-tolerant loop, auto-resume.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-1.3b \
        --smoke --steps 200 --ckpt-dir /tmp/ckpt --resume auto

Fault tolerance in the loop:
  * checkpoint every --ckpt-every steps (sharded npz + manifest);
  * SIGTERM (preemption) triggers a final checkpoint at the step boundary;
  * --resume auto restores the latest complete checkpoint; the data stream
    is a pure function of (seed, step) so no data state is needed;
  * a step-time watchdog logs stragglers (steps slower than
    --straggler-factor x the running median are flagged; on a real fleet
    this feeds the controller that evicts/replaces the slow host), with
    the compiles that fell inside the step (``CompileCounter``).

Each step runs under the profiler's step annotation with one span per
host phase (``launch/trace_names.py``); ``--profile-dir DIR
--profile-steps A:B`` traces steps A to B into DIR (docs/tracing.md).
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_mod
from repro import configs, optim
from repro.configs import adapters
from repro.core.dropout_plan import DropoutPlan
from repro.data import synthetic
from repro.distributed import sharding as shd
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.launch import trace_names as tn


def make_batch_fn(spec, cfg, batch: int, seq: int, seed: int):
    vocab = getattr(cfg, "vocab", None) or getattr(cfg, "src_vocab", 256)

    if spec.kind in ("transformer", "xlstm", "ssm", "lstm_lm"):
        stream = synthetic.lm_stream(vocab, batch * (seq + 1) * 64, seed=seed)

        def fn(step):
            n = batch * (seq + 1)
            off = (step * n) % (len(stream) - n - 1)
            chunk = stream[off:off + n].reshape(batch, seq + 1)
            d = {"tokens": chunk[:, :-1], "labels": chunk[:, 1:]}
            if getattr(cfg, "embeds_in", False):
                rng = np.random.default_rng(seed + step)
                d["embeds"] = rng.standard_normal(
                    (batch, seq, cfg.d_model), dtype=np.float32)
                del d["tokens"]
            if getattr(cfg, "is_encoder_decoder", False):
                rng = np.random.default_rng(seed + step)
                d["frames"] = rng.standard_normal(
                    (batch, cfg.enc_seq, cfg.d_model),
                    dtype=np.float32) * 0.02
            return d
        return fn
    if spec.kind == "nmt":
        def fn(step):
            return synthetic.nmt_pairs(batch, cfg.src_vocab, cfg.tgt_vocab,
                                       max_len=seq, seed=seed + step)
        return fn
    if spec.kind == "tagger":
        def fn(step):
            return synthetic.ner_examples(batch, cfg.vocab, cfg.char_vocab,
                                          cfg.num_tags, seq=seq,
                                          seed=seed + step)
        return fn
    raise ValueError(spec.kind)


class CompileCounter:
    """Counts XLA compiles, and their seconds, while it is entered: a
    ``jax.monitoring`` listener on the event JAX times around each
    backend compile (a load from the persistent compilation cache
    included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.seconds = 0, 0.0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


class TrainLoop:
    """The body of ``main``'s loop: one call of ``step`` is one training
    step. The batch goes to the device leaf by leaf, the jitted step is
    called with ``jnp.int32(step)`` and ``fold_in(key, step)``, the loss
    is synced with ``float``, the step's time goes to the straggler
    watchdog and the log, and ``save(step, params, opt_state)`` (the
    checkpoint; true to stop) closes it. The step runs under the
    profiler's ``StepTraceAnnotation`` with a span per phase
    (``trace_names.SPANS``); outside a trace they cost next to nothing.
    Compiles are counted from the end of the first step."""

    def __init__(self, jitted: Callable, batch_fn: Callable, key, *,
                 compiles: CompileCounter, straggler_factor: float = 3.0,
                 log_every: int = 10, save: Optional[Callable] = None):
        self.jitted, self.batch_fn, self.key = jitted, batch_fn, key
        self.straggler_factor, self.log_every = straggler_factor, log_every
        self.save, self.compiles = save, compiles
        self.times = []
        self.base = None        # (compiles, seconds) after the first step

    def compiles_after_first(self):
        """(compiles, seconds) since the end of the first step."""
        if self.base is None:
            return 0, 0.0
        return (self.compiles.n - self.base[0],
                self.compiles.seconds - self.base[1])

    def step(self, step: int, params, opt_state):
        """-> (params, opt_state, loss, stop)."""
        span = jax.profiler.TraceAnnotation
        n0, s0 = self.compiles.n, self.compiles.seconds
        with jax.profiler.StepTraceAnnotation(tn.STEP, step_num=step):
            t0 = time.time()
            with span(tn.BATCH):
                batch = jax.tree.map(jnp.asarray, self.batch_fn(step))
            with span(tn.ARGS):
                args = (jnp.int32(step), jax.random.fold_in(self.key, step))
            with span(tn.DISPATCH):
                params, opt_state, loss = self.jitted(params, opt_state,
                                                      batch, *args)
            with span(tn.SYNC):
                loss = float(loss)
            dt = time.time() - t0
            self.times.append(dt)
            med = float(np.median(self.times[-50:]))
            if dt > self.straggler_factor * med and len(self.times) > 10:
                print(f"[straggler] step {step} took {dt:.2f}s "
                      f"(median {med:.2f}s; {self.compiles.n - n0} "
                      f"compiles, {self.compiles.seconds - s0:.2f}s) — "
                      f"flagged for controller")
            if step % self.log_every == 0:
                print(f"step {step:5d}  loss {loss:.4f}  {dt*1e3:.0f} ms")
            with span(tn.CKPT):
                stop = bool(self.save and self.save(step, params, opt_state))
        if self.base is None:
            self.base = (self.compiles.n, self.compiles.seconds)
        return params, opt_state, loss, stop


def profile_steps(text: str):
    """'A:B' -> (A, B), the first and last step to trace."""
    a, b = (int(x) for x in text.split(":"))
    if not 0 <= a <= b:
        raise ValueError(f"need 0 <= A <= B, got {text!r}")
    return a, b


def main(argv=None):
    compile_cache.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-dropout", action="store_true")
    ap.add_argument("--dropout", default="",
                    help="dropout-plan override: 'case{1..4}:<rate>[:bs<int>]"
                         "[:pallas]' (e.g. case3:0.5:bs128) or 'off'; applies "
                         "the case at the arch's canonical sites")
    ap.add_argument("--engine", default="",
                    choices=["", "scheduled", "stepwise", "fused"],
                    help="recurrent-engine override: 'scheduled' (two-phase: "
                         "masks + NR matmuls hoisted out of the scan), "
                         "'fused' (Phase B as one persistent-scan kernel "
                         "per layer) or 'stepwise' (in-scan reference); "
                         "applies to the recurrent archs, no-op elsewhere")
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--mesh", default="",
                    help="data-parallel sharded training: 'auto' (all host "
                         "devices) or an int device count. Runs the step "
                         "under shard_map — batch sharded over 'data', "
                         "params/U replicated, grads psum'd exactly "
                         "(docs/distributed.md). Recurrent archs only; "
                         "--batch must divide by the mesh size")
    ap.add_argument("--profile-dir", default="",
                    help="write a profiler trace of --profile-steps here "
                         "(docs/tracing.md)")
    ap.add_argument("--profile-steps", type=profile_steps, default=None,
                    help="'A:B': trace steps A to B, both included, with "
                         "the Python tracer off")
    args = ap.parse_args(argv)
    prof = args.profile_steps
    if bool(args.profile_dir) != (prof is not None):
        ap.error("--profile-dir and --profile-steps go together")

    spec = configs.get_arch(args.arch)
    cfg = spec.smoke() if args.smoke else spec.full()
    if args.dropout:
        cfg = adapters.apply_dropout(spec, cfg, args.dropout)
        print(f"[dropout] plan override {args.dropout!r} -> sites "
              f"{list(cfg.plan.active_sites())}")
    if args.engine:
        cfg = adapters.apply_engine(spec, cfg, args.engine)
        if spec.kind in adapters.ENGINE_KINDS:
            print(f"[engine] recurrent engine -> {cfg.engine!r}")
    if args.mesh:
        n_dev = (len(jax.devices()) if args.mesh == "auto"
                 else int(args.mesh))
        mesh = mesh_mod.make_data_mesh(n_dev)
        print(f"[mesh] data-parallel over {n_dev} device(s)")
    else:
        mesh = mesh_mod.make_host_mesh()
    rules = shd.rules_for_mesh(mesh)

    init_fn, p_shapes, p_shard, _ = steps_mod.param_setup(
        spec, cfg, mesh, rules, seed=args.seed)
    _, n_pin, pin_bytes = steps_mod.grad_layout_pins(p_shapes)
    what = ("leaf pinned to its parameter's layout" if n_pin == 1 else
            "leaves pinned to their parameters' layouts")
    print(f"[layout] {n_pin} gradient {what} ({pin_bytes / 1e6:.1f} MB)")
    opt = optim.chain(optim.clip_by_global_norm(1.0), optim.adamw(args.lr))
    if args.mesh:
        train_step = steps_mod.make_sharded_train_step(
            spec, cfg, opt, mesh, rules=rules,
            use_dropout=not args.no_dropout)
    else:
        train_step = steps_mod.make_train_step(
            spec, cfg, opt, rules, use_dropout=not args.no_dropout)
    jitted = jax.jit(train_step, donate_argnums=(0, 1))

    params = init_fn()
    opt_state = opt.init(params)
    start = 0

    hook = ckpt_mod.PreemptionHook()
    if args.ckpt_dir and args.resume == "auto":
        latest = ckpt_mod.latest_step(args.ckpt_dir)
        if latest is not None:
            (params, opt_state), start = ckpt_mod.restore_checkpoint(
                args.ckpt_dir, (params, opt_state))
            print(f"[resume] restored step {start} from {args.ckpt_dir}")

    batch_fn = make_batch_fn(spec, cfg, args.batch, args.seq, args.seed)
    key = jax.random.PRNGKey(args.seed)
    # record the pattern that actually RAN: --no-dropout withholds the key,
    # so every site is inactive regardless of the config's plan
    ckpt_meta = None
    if hasattr(cfg, "plan"):
        plan_ran = DropoutPlan.off() if args.no_dropout else cfg.plan
        ckpt_meta = {"dropout_plan": plan_ran.to_dict()}

    def save(step, params, opt_state):
        if args.ckpt_dir and ((step + 1) % args.ckpt_every == 0
                              or hook.should_save or step + 1 == args.steps):
            ckpt_mod.save_checkpoint(args.ckpt_dir, step + 1,
                                     (params, opt_state), meta=ckpt_meta)
            if hook.should_save:
                print(f"[preempt] final checkpoint at step {step+1}; exiting")
                return True
        return False

    loss = float("nan")   # resume past end of run: no step executes
    tracing = False
    with CompileCounter() as compiles:
        loop = TrainLoop(jitted, batch_fn, key, compiles=compiles,
                         straggler_factor=args.straggler_factor,
                         log_every=args.log_every, save=save)
        t_train0 = time.time()
        try:
            for step in range(start, args.steps):
                if prof and step == prof[0]:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(args.profile_dir,
                                             create_perfetto_trace=True,
                                             profiler_options=opts)
                    tracing = True
                params, opt_state, loss, stop = loop.step(step, params,
                                                          opt_state)
                if tracing and step == prof[1]:
                    jax.profiler.stop_trace()
                    tracing = False
                if stop:
                    return 0
        finally:
            if tracing:
                jax.profiler.stop_trace()
    total = time.time() - t_train0
    n_run = max(args.steps - start, 0)
    n_comp, s_comp = loop.compiles_after_first()
    print(f"done: {n_run} steps in {total:.1f}s "
          f"({n_run/max(total,1e-9):.2f} steps/s), "
          f"final loss {loss:.4f}, {n_comp} compiles after the first step "
          f"({s_comp:.2f}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
