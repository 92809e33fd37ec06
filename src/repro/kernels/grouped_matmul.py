"""Grouped (per-expert) matmul Pallas kernel — the MoE dispatch fused away.

EXPERIMENTS §Perf (mixtral iteration 3) measured ~30% of the post-local-
routing memory term as pure dispatch movement (gathers/scatters/slices
around the expert matmul). This kernel removes it: after the per-shard
sort, every expert's tokens are CONTIGUOUS rows of the sorted buffer, so
the expert compute is

    y[i] = x_sorted[i] @ w[expert_of_row(i)]

with no (E, C, D) capacity buffer at all. The only metadata is a per-row-
block expert id (row blocks never straddle experts because the host pads
each expert's count to the block size), scalar-prefetched into SMEM and
used by the W BlockSpec index_map — the same zero-cost-gather pattern as
``gather_matmul``.

Grid (T/bm, F/bf, D/bk), K innermost, fp32 VMEM accumulator. Validated in
interpret mode against ``grouped_matmul_ref`` (tests/test_grouped.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _kernel(blk_e_ref, x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bf", "bk", "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, blk_expert: jax.Array, *,
                   bm: int = 128, bf: Optional[int] = None,
                   bk: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """x: (T, D) expert-sorted rows (T % bm == 0, blocks expert-pure);
    w: (E, D, F); blk_expert: (T//bm,) int32 expert id per row block.
    -> y: (T, F)."""
    interpret = resolve_interpret(interpret)
    T, D = x.shape
    E, _, F = w.shape
    assert T % bm == 0, (T, bm)
    bf = bf or min(128, F)
    bk = bk or min(128, D)
    assert F % bf == 0 and D % bk == 0, (F, bf, D, bk)
    grid = (T // bm, F // bf, D // bk)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k, be: (i, k))
    w_spec = pl.BlockSpec((1, bk, bf), lambda i, j, k, be: (be[i], k, j))
    o_spec = pl.BlockSpec((bm, bf), lambda i, j, k, be: (i, j))

    def kernel(be_ref, x_ref, w_ref, o_ref, acc_ref):
        _kernel(be_ref, x_ref, w_ref.at[0], o_ref, acc_ref, nk=grid[2])

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[x_spec, w_spec],
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        interpret=interpret,
    )(blk_expert, x, w)


def plan_groups(counts: jax.Array, bm: int, capacity_blocks: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Host/trace-side helper: per-expert token counts -> (row offsets into
    the padded sorted buffer, per-row-block expert ids).

    Each expert's region is padded up to a multiple of ``bm`` and capped at
    ``capacity_blocks`` blocks, so row blocks are expert-pure and the total
    padded length is static: T_pad = E * capacity_blocks * bm.
    """
    E = counts.shape[0]
    blocks = jnp.clip((counts + bm - 1) // bm, 0, capacity_blocks)
    # static layout: expert e owns block slots [e*capacity_blocks, ...)
    blk_expert = jnp.repeat(jnp.arange(E, dtype=jnp.int32), capacity_blocks)
    offsets = jnp.arange(E, dtype=jnp.int32) * capacity_blocks * bm
    return offsets, blk_expert
