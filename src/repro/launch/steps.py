"""Step builders: train_step / prefill_step / serve_step with shardings.

Everything the dry-run lowers and the drivers execute is built here, so the
compiled artifact is identical in both paths. Parameters, optimizer state,
batches and decode state all get NamedShardings derived from the logical
axes + rules; train_step donates (params, opt_state), serve_step donates the
decode state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import adapters
from repro.configs.base import ArchSpec
from repro.configs.shapes import ShapeSpec
from repro.distributed import sharding as shd


def replicated(mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# parameter / optimizer / batch shardings
# ---------------------------------------------------------------------------


def param_setup(spec: ArchSpec, cfg, mesh: Mesh, rules: shd.ShardingRules,
                seed: int = 0):
    """-> (init_fn() -> params, param_shapes, param_shardings, axes_tree).

    init is deferred (callable) so the dry-run can eval_shape it without
    allocating 480B parameters.
    """
    key = jax.random.PRNGKey(seed)

    def init_tagged():
        return adapters.init_params(spec.kind, key, cfg)

    tagged_shapes = jax.eval_shape(init_tagged)
    shapes, axes = shd.unzip(tagged_shapes)
    shardings = shd.make_shardings(axes, rules, mesh, shapes)

    def init_fn():
        return shd.strip(init_tagged())

    return init_fn, shapes, shardings, axes


def opt_state_shardings(opt_state_shapes, param_shardings, mesh):
    """Mirror param shardings onto optimizer-state trees (m/v/avg), scalars
    replicated. Handles our optimizers' state shapes + chain tuples."""
    rep = replicated(mesh)

    def walk(s):
        if isinstance(s, tuple):
            return tuple(walk(x) for x in s)
        if isinstance(s, dict):
            out = {}
            for k, v in s.items():
                if k in ("m", "v", "avg"):
                    out[k] = param_shardings
                else:
                    out[k] = jax.tree.map(lambda _: rep, v)
            return out
        return jax.tree.map(lambda _: rep, s)

    return walk(opt_state_shapes)


def batch_shardings(spec: ArchSpec, cfg, shape: ShapeSpec, mesh: Mesh,
                    rules: shd.ShardingRules, specs=None):
    specs = specs or adapters.train_batch_specs(spec, cfg, shape)
    axes = adapters.batch_logical_axes(spec, cfg, shape)
    return {k: NamedSharding(
        mesh, shd.logical_to_pspec(axes[k], rules, specs[k].shape, mesh))
        for k in specs}


def decode_state_shardings(spec: ArchSpec, cfg, state_shapes, mesh, rules):
    axes = adapters.decode_state_axes(spec, cfg)
    return {k: NamedSharding(
        mesh, shd.logical_to_pspec(axes[k], rules,
                                   state_shapes[k].shape, mesh))
        for k in state_shapes}


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------


def layout_device():
    """The device whose default layouts the train step keeps."""
    return jax.devices()[0]


def grad_layout_pins(tree):
    """Which gradient leaves the train step pins to their stored layout.

    -> (pins, n_pinned, nbytes): ``pins`` has, per leaf of ``tree`` (the
    gradient, or the parameters it mirrors) in flattening order, the
    device's default layout for the leaf's shape and dtype where that
    layout is not row-major, else None; ``n_pinned`` and ``nbytes`` count
    those leaves and their bytes. The leaves may be arrays, tracers or
    ShapeDtypeStructs. The layout is the whole leaf's, which is its
    shard's as long as the parameters are replicated."""
    device = layout_device()
    pins, n, nbytes = [], 0, 0
    for x in jax.tree.leaves(tree):
        lay = None
        if x.ndim > 1:
            lay = Layout.from_pjrt_layout(device.client.get_default_layout(
                x.dtype, x.shape, device))
            if lay.major_to_minor == tuple(range(x.ndim)):
                lay = None
            else:
                n, nbytes = n + 1, nbytes + x.size * x.dtype.itemsize
        pins.append(lay)
    return pins, n, nbytes


def pin_grad_layouts(grads):
    """``grads`` with each leaf constrained to the layout its parameter
    and AdamW moments are stored in (``grad_layout_pins``).

    The gradient of an embedding lookup comes out of a scatter-add
    row-major, while a v5e stores a tall, narrow f32 table column-major.
    Unconstrained, XLA carries the gradient's layout through the
    elementwise optimizer update and transposes the table and both
    moments in and out every step; constrained, it transposes the
    gradient once. On the CPU every default is row-major and nothing is
    constrained."""
    leaves, tree = jax.tree.flatten(grads)
    pins = grad_layout_pins(leaves)[0]
    return jax.tree.unflatten(tree, [
        g if lay is None else with_layout_constraint(g, lay)
        for g, lay in zip(leaves, pins)])


def make_train_step(spec: ArchSpec, cfg, opt: optim.optimizers.Optimizer,
                    rules: Optional[shd.ShardingRules], *,
                    n_micro: int = 1, use_dropout: bool = True):
    """(params, opt_state, batch, step, key) -> (params, opt_state, loss)."""
    lfn = adapters.loss_fn(spec.kind)
    grad_fn = optim.gradient_accumulation(
        lambda p, b, **kw: lfn(p, b, cfg, rules=rules, **kw), n_micro)

    def train_step(params, opt_state, batch, step, key):
        loss, grads = grad_fn(params, batch,
                              drop_key=key if use_dropout else None,
                              step=step)
        grads = pin_grad_layouts(grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def make_sharded_loss_and_grad(spec_or_kind, cfg, mesh: Mesh, *,
                               rules: Optional[shd.ShardingRules] = None,
                               use_dropout: bool = True):
    """(params, batch, step, key) -> (loss, grads) under batch-sharded
    shard_map — loss and grads match the single-device ``loss_fn`` allclose
    (exactly, in exact arithmetic; see distributed/data_parallel.py).

    ``spec_or_kind`` is an ArchSpec or a kind string; only the recurrent
    families (``adapters.SHARD_KINDS``) have the shard-safe dropout path.
    """
    from repro.distributed import data_parallel as dp
    kind = getattr(spec_or_kind, "kind", spec_or_kind)
    if kind not in adapters.SHARD_KINDS:
        raise ValueError(f"{kind!r} has no sharded train path; "
                         f"supported: {adapters.SHARD_KINDS}")
    lfn = adapters.loss_fn(kind)
    wfn = adapters.loss_weight(kind)

    def local_loss(params, batch, step, key, shard):
        return lfn(params, batch, cfg, rules=rules,
                   drop_key=key if use_dropout else None,
                   step=step, shard=shard)

    return dp.sharded_value_and_grad(
        local_loss, lambda b: wfn(b, cfg), mesh)


def make_sharded_train_step(spec_or_kind, cfg,
                            opt: optim.optimizers.Optimizer, mesh: Mesh, *,
                            rules: Optional[shd.ShardingRules] = None,
                            use_dropout: bool = True):
    """Data-parallel twin of ``make_train_step``: same signature
    ``(params, opt_state, batch, step, key) -> (params, opt_state, loss)``,
    with loss/grads computed under shard_map on ``mesh`` (params and
    optimizer state replicated, batch sharded, grads psum'd exactly)."""
    grad_fn = make_sharded_loss_and_grad(spec_or_kind, cfg, mesh,
                                         rules=rules,
                                         use_dropout=use_dropout)

    def train_step(params, opt_state, batch, step, key):
        loss, grads = grad_fn(params, batch, step, key)
        grads = pin_grad_layouts(grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def make_prefill_step(spec: ArchSpec, cfg, rules):
    f = adapters.prefill_fn(spec)

    def prefill_step(params, batch, state):
        feats, state = f(params, batch, cfg, state, rules=rules)
        return feats, state

    return prefill_step


def make_serve_step(spec: ArchSpec, cfg, rules):
    decode = adapters.decode_fn(spec)

    def serve_step(params, state, tokens, pos):
        logits, state = decode(params, cfg, state, tokens, pos, rules=rules)
        return logits, state

    return serve_step


# ---------------------------------------------------------------------------
# lowering bundles (shared by dryrun + drivers)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LoweredCell:
    kind: str                    # train | prefill | decode
    jitted: Any
    example_args: tuple          # ShapeDtypeStructs suitable for .lower()
    donate: tuple = ()


def default_opt(cfg) -> optim.optimizers.Optimizer:
    return optim.chain(optim.clip_by_global_norm(1.0),
                       optim.adamw(1e-4, weight_decay=0.01))


def build_cell(spec: ArchSpec, cfg, shape: ShapeSpec, mesh: Mesh,
               rules: shd.ShardingRules, *, use_dropout: bool = True,
               n_micro: int = 1, dropout: str = "",
               engine: str = "") -> LoweredCell:
    """Assemble the jitted step + abstract inputs for one (arch, shape).

    ``dropout`` is an optional CLI-style plan override ("case3:0.5:bs128")
    applied to the config before lowering, so dry-runs/perf sweeps lower the
    exact plan the trainer would run. ``engine`` likewise overrides the
    recurrent execution engine ("scheduled" | "stepwise" | "fused") on the kinds that
    have one.
    """
    if dropout:
        cfg = adapters.apply_dropout(spec, cfg, dropout)
    if engine:
        cfg = adapters.apply_engine(spec, cfg, engine)
    init_fn, p_shapes, p_shard, _ = param_setup(spec, cfg, mesh, rules)
    rep = replicated(mesh)

    if shape.kind == "train":
        opt = default_opt(cfg)
        o_shapes = jax.eval_shape(opt.init, p_shapes)
        o_shard = opt_state_shardings(o_shapes, p_shard, mesh)
        b_specs = adapters.train_batch_specs(spec, cfg, shape)
        b_shard = batch_shardings(spec, cfg, shape, mesh, rules, b_specs)
        fn = make_train_step(spec, cfg, opt, rules, n_micro=n_micro,
                             use_dropout=use_dropout)
        jitted = jax.jit(
            fn,
            in_shardings=(p_shard, o_shard, b_shard, rep, rep),
            out_shardings=(p_shard, o_shard, rep),
            donate_argnums=(0, 1))
        args = (p_shapes, o_shapes, b_specs,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        return LoweredCell("train", jitted, args, donate=(0, 1))

    if shape.kind == "prefill":
        state_shapes = adapters.decode_state_specs(
            spec, cfg, shape.global_batch, shape.seq_len)
        s_shard = decode_state_shardings(spec, cfg, state_shapes, mesh, rules)
        b_specs = adapters.prefill_batch_specs(spec, cfg, shape)
        b_shard = batch_shardings(spec, cfg, shape, mesh, rules, b_specs)
        fn = make_prefill_step(spec, cfg, rules)
        jitted = jax.jit(fn,
                         in_shardings=(p_shard, b_shard, s_shard),
                         donate_argnums=(2,))
        args = (p_shapes, b_specs, state_shapes)
        return LoweredCell("prefill", jitted, args, donate=(2,))

    # decode
    state_shapes = adapters.decode_state_specs(
        spec, cfg, shape.global_batch, shape.seq_len)
    s_shard = decode_state_shardings(spec, cfg, state_shapes, mesh, rules)
    tok = adapters.decode_token_specs(spec, cfg, shape)
    tok_shard = NamedSharding(
        mesh, shd.logical_to_pspec(("batch", "seq", None)[:len(tok.shape)],
                                   rules, tok.shape, mesh))
    fn = make_serve_step(spec, cfg, rules)
    jitted = jax.jit(fn,
                     in_shardings=(p_shard, s_shard, tok_shard, rep),
                     donate_argnums=(1,))
    args = (p_shapes, state_shapes, tok,
            jax.ShapeDtypeStruct((), jnp.int32))
    return LoweredCell("decode", jitted, args, donate=(1,))
