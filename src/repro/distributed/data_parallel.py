"""Data-parallel shard_map wrapper for the recurrent training engines.

``sharded_value_and_grad`` puts a per-shard loss under ``shard_map`` on the
batch axes of a ("data", "model") / ("pod", "data", "model") mesh and
combines shards EXACTLY: every supported loss is a weighted mean
``sum(elems * m) / max(sum(m), 1)`` (configs/adapters.py ``loss_weight``),
so with per-shard weight ``w_i`` and local loss ``l_i``

    global_loss  = psum(l_i * w_i) / max(psum(w_i), 1)
    global_grads = psum(grad(l_i * w_i)) / max(psum(w_i), 1)

reproduces the single-device loss and gradients bit-for-bit in exact
arithmetic — ragged batches and all-pad shards included (an all-dummy
shard has ``l_i = 0`` from the clamped local denominator and ``w_i = 0``,
so its contribution ``l_i * w_i = 0`` equals its true masked sum). The
weights carry no parameter dependence, so the product rule adds nothing.

What replicates vs shards (the MaskSchedule shard-safety contract):

  * params + the recurrent weight U: replicated (``P()`` in_specs) — every
    shard runs the full scan on its batch rows; grads psum across shards.
  * batch leaves: dim 0 sharded over the batch axes ("pod", "data").
  * structured keep-block tables (case3/case4): batch-independent by
    construction — each shard resamples the identical table from the same
    site key (free replication, no communication).
  * dense per-row bitmasks (case1/case2): the local loss binds the plan
    with a ``BatchShard`` so each shard samples the GLOBAL mask and keeps
    its contiguous row block — bit-identical rows to the unsharded run
    (core/dropout_plan.py, "Batch sharding").

Non-divisible batches raise ``ValueError`` here, at the entry, with the
offending leaves named — not as an opaque XLA reshape error mid-lowering.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.dropout_plan import BatchShard

# Mesh axes a batch dim shards over, in linearization order (the same
# physical mapping distributed/sharding.py DEFAULT_RULES gives "batch").
BATCH_AXES = ("pod", "data")


def batch_axes(mesh: Mesh) -> tuple:
    """The subset of BATCH_AXES this mesh actually has, in order."""
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)


def shard_count(mesh: Mesh, axes: Optional[Sequence[str]] = None) -> int:
    """Static number of batch shards (product of the batch-axis sizes)."""
    axes = batch_axes(mesh) if axes is None else tuple(axes)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def shard_index(mesh: Mesh, axes: Sequence[str]):
    """This shard's linearized batch-axis index (traced int32; call only
    inside shard_map). Row-major over ``axes``, matching how shard_map
    assigns dim-0 blocks to ``P(axes)``."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def check_batch_divisible(batch: dict, n_shards: int) -> None:
    """Raise a clear ValueError when any batch leaf's dim 0 can't split
    into ``n_shards`` equal blocks (the failure would otherwise surface as
    an opaque XLA reshape error from inside shard_map lowering)."""
    if n_shards <= 1:
        return
    bad = {k: tuple(v.shape) for k, v in batch.items()
           if getattr(v, "ndim", 0) >= 1 and v.shape[0] % n_shards != 0}
    if bad:
        raise ValueError(
            f"batch dim 0 must be divisible by the {n_shards} batch shards "
            f"of the mesh; offending leaves: {bad}. Pad or rebatch (see "
            f"docs/distributed.md).")


def batch_pspecs(batch: dict, axes: Sequence[str]) -> dict:
    """PartitionSpecs sharding every array leaf's dim 0 over ``axes``."""
    ax = tuple(axes)
    return {k: P(ax) if getattr(v, "ndim", 0) >= 1 else P()
            for k, v in batch.items()}


def sharded_value_and_grad(loss_fn: Callable, weight_fn: Callable,
                           mesh: Mesh, *,
                           axes: Optional[Sequence[str]] = None) -> Callable:
    """Build ``(params, batch, step, key) -> (loss, grads)`` under shard_map.

    ``loss_fn(params, local_batch, step, key, shard)`` returns the LOCAL
    weighted-mean loss (a model loss_fn with cfg/rules closed over, the
    ``shard`` kwarg threading the BatchShard into ``DropoutPlan.bind``).
    ``weight_fn(local_batch)`` returns its weight (the un-clamped local
    denominator). Params arrive replicated; batch leaves shard dim 0.
    """
    axes = batch_axes(mesh) if axes is None else tuple(axes)
    n = shard_count(mesh, axes)

    def local(params, batch, step, key):
        shard = BatchShard(index=shard_index(mesh, axes), count=n)
        w = jnp.float32(weight_fn(batch))
        lsum, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, step, key, shard)
            * w.astype(jnp.float32))(params)
        wsum = jax.lax.psum(w, axes) if axes else w
        denom = jnp.maximum(wsum, 1.0)
        if axes:
            lsum = jax.lax.psum(lsum, axes)
            grads = jax.tree.map(lambda g: jax.lax.psum(g, axes), grads)
        loss = lsum / denom
        grads = jax.tree.map(lambda g: (g / denom).astype(g.dtype), grads)
        return loss, grads

    def vag(params, batch, step, key):
        check_batch_divisible(batch, n)
        f = jax.shard_map(local, mesh=mesh,
                          in_specs=(P(), batch_pspecs(batch, axes), P(), P()),
                          out_specs=(P(), P()),
                          check_vma=False)
        return f(params, batch, step, key)

    return vag
