"""Block-gather matmul Pallas TPU kernel — the paper's compaction, fused.

The structured dropout mask is a set of kept hidden-unit *blocks* (masks.py).
Rather than materializing compacted copies of the operands in HBM, this kernel
gathers kept blocks on the fly through the ``BlockSpec index_map`` using the
kept-block ids scalar-prefetched into SMEM: the gather costs nothing beyond
the (1-p)-sized matmul itself.

Three variants cover the three training phases (sparse_matmul.py):
  FP  : y  = a[:, kept] @ b[kept, :]   (gather="b_rows")   input  sparsity
  BP  : dx = dy @ b[kept, :].T         (gather="b_rows", transpose_b)
                                                           output sparsity
  FFN : y  = a @ b[:, kept]            (gather="b_cols")   output sparsity
(The WG matmul needs no gather — its inputs are already compact.)

``gather_matmul_stepped`` extends the FP/BP variants to a whole *schedule*
of masks (the scheduled recurrent engine's Phase A): ``keep_blocks`` is a
``(T, nk)`` ids table and ``a`` carries a leading time axis. T becomes an
extra leading grid axis and the table is scalar-prefetched whole, so each
step's gather is resolved in the BlockSpec ``index_map`` (``ids[t, k]``) at
zero cost beyond the (1-p)-sized matmuls themselves — no per-step weight
copies ever land in HBM.

Tiling: grid = (M/bm, OUT/b_out, CONTRACT/b_k), k innermost; fp32 VMEM
accumulator, write-out on the last k step. The dropout ``block_size`` doubles
as the gathered dimension's tile, so on TPU it must be a multiple of 128 (a
lane-dimension tile); any other size raises ``ValueError`` at trace time
rather than failing inside Mosaic. Interpret mode takes any size.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _mm_kernel(ids_ref, a_ref, b_ref, o_ref, acc_ref, *, n_k: int, transpose_b: bool):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if transpose_b:
        b = b.T
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


LANES = 128   # lane width of a TPU vreg: the minor dimension of a tile


def _check_tile(block_size, gathered, interpret):
    """Raise at trace time when ``block_size`` cannot tile the kernel on TPU.

    The block size is the tile of the gathered dimension, and in every
    variant some operand holds that tile on its lanes, which Mosaic takes
    only in multiples of 128 (or whole). Interpret mode takes any size.
    """
    if interpret or block_size % LANES == 0 or block_size == gathered:
        return
    raise ValueError(
        f"block_size={block_size} cannot tile gather_matmul on TPU: the "
        f"kernel tiles the gathered dimension ({gathered}) by the block "
        f"size, which must be a multiple of the {LANES}-lane tile there; "
        f"use such a block size or impl='xla'")


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=(
    "block_size", "gather", "a_is_compact", "transpose_b", "bm", "bn", "bk",
    "interpret"))
def gather_matmul(a: jax.Array, b: jax.Array, keep_blocks: jax.Array, *,
                  block_size: int,
                  gather: str = "b_rows",
                  a_is_compact: bool = False,
                  transpose_b: bool = False,
                  bm: Optional[int] = None,
                  bn: Optional[int] = None,
                  bk: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    """See module docstring. a: (M, Ka), b: (K, N), keep_blocks: (nk,) int32."""
    interpret = resolve_interpret(interpret)
    _check_tile(block_size, b.shape[1 if gather == "b_cols" else 0],
                interpret)
    nk = keep_blocks.shape[0]
    bs = block_size
    M = a.shape[0]
    bm = bm or min(128, M)
    a = _pad_to(a, 0, bm)
    Mp = a.shape[0]
    gm = Mp // bm

    if gather == "b_rows" and not transpose_b:
        # y (M, N) = a_c (M, nk*bs) @ b[kept, :] (nk*bs, N); contract over kept.
        N = b.shape[1]
        bn = bn or min(128, N)
        b = _pad_to(b, 1, bn)
        gn = b.shape[1] // bn
        grid = (gm, gn, nk)
        if a_is_compact:
            a_spec = pl.BlockSpec((bm, bs), lambda i, j, k, ids: (i, k))
        else:
            a_spec = pl.BlockSpec((bm, bs), lambda i, j, k, ids: (i, ids[k]))
        b_spec = pl.BlockSpec((bs, bn), lambda i, j, k, ids: (ids[k], j))
        o_spec = pl.BlockSpec((bm, bn), lambda i, j, k, ids: (i, j))
        out_shape = jax.ShapeDtypeStruct((Mp, b.shape[1]), a.dtype)
        acc = pltpu.VMEM((bm, bn), jnp.float32)
        n_k, out_slice = nk, (slice(0, M), slice(0, N))
    elif gather == "b_rows" and transpose_b:
        # y (M, nk*bs) = a (M, N) @ b[kept, :].T; contract over N.
        N = a.shape[1]
        bk = bk or min(128, N)
        a = _pad_to(a, 1, bk)
        b = _pad_to(b, 1, bk)
        gk = a.shape[1] // bk
        grid = (gm, nk, gk)
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, k, ids: (i, k))
        b_spec = pl.BlockSpec((bs, bk), lambda i, j, k, ids: (ids[j], k))
        o_spec = pl.BlockSpec((bm, bs), lambda i, j, k, ids: (i, j))
        out_shape = jax.ShapeDtypeStruct((Mp, nk * bs), a.dtype)
        acc = pltpu.VMEM((bm, bs), jnp.float32)
        n_k, out_slice = gk, (slice(0, M), slice(None))
    elif gather == "b_cols":
        # y (M, nk*bs) = a (M, K) @ b[:, kept]; contract over K.
        K = b.shape[0]
        bk = bk or min(128, K)
        a = _pad_to(a, 1, bk)
        b = _pad_to(b, 0, bk)
        gk = b.shape[0] // bk
        grid = (gm, nk, gk)
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, k, ids: (i, k))
        b_spec = pl.BlockSpec((bk, bs), lambda i, j, k, ids: (k, ids[j]))
        o_spec = pl.BlockSpec((bm, bs), lambda i, j, k, ids: (i, j))
        out_shape = jax.ShapeDtypeStruct((Mp, nk * bs), a.dtype)
        acc = pltpu.VMEM((bm, bs), jnp.float32)
        n_k, out_slice = gk, (slice(0, M), slice(None))
    else:
        raise ValueError(f"bad gather={gather!r} transpose_b={transpose_b}")

    kernel = functools.partial(_mm_kernel, n_k=n_k,
                               transpose_b=(gather == "b_rows" and transpose_b))
    y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[a_spec, b_spec],
            out_specs=o_spec,
            scratch_shapes=[acc],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(keep_blocks, a, b)
    return y[out_slice]


# ---------------------------------------------------------------------------
# Scheduled (per-step ids table) variant
# ---------------------------------------------------------------------------


def _mm_kernel_stepped(ids_ref, a_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                       transpose_b: bool):
    """Grid (T, gm, g_out, g_contract); contraction innermost (axis 3)."""
    del ids_ref  # consumed by the index_maps
    @pl.when(pl.program_id(3) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0]
    b = b_ref[...]
    if transpose_b:
        b = b.T
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_size", "a_is_compact", "transpose_b", "bm", "bn", "bk",
    "interpret"))
def gather_matmul_stepped(a: jax.Array, b: jax.Array, keep_blocks: jax.Array,
                          *,
                          block_size: int,
                          a_is_compact: bool = False,
                          transpose_b: bool = False,
                          bm: Optional[int] = None,
                          bn: Optional[int] = None,
                          bk: Optional[int] = None,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Per-step "b_rows" gather matmuls for a whole mask schedule.

    keep_blocks: (T, nk) int32 — step ``t`` contracts over its own kept
    blocks. Two variants (mirroring gather_matmul):

      not transpose_b (FP): a (T, M, nk*bs | K) -> y (T, M, N) = a_c @ b[kept_t]
      transpose_b     (BP): a (T, M, N)         -> y (T, M, nk*bs) = a @ b[kept_t].T
    """
    interpret = resolve_interpret(interpret)
    _check_tile(block_size, b.shape[0], interpret)
    T, nk = keep_blocks.shape
    bs = block_size
    assert a.shape[0] == T, (a.shape, T)
    M = a.shape[1]
    bm = bm or min(128, M)
    a = _pad_to(a, 1, bm)
    gm = a.shape[1] // bm

    if not transpose_b:
        # y (T, M, N) = a_c (T, M, nk*bs) @ b[kept_t, :]; contract over kept.
        N = b.shape[1]
        bn = bn or min(128, N)
        b = _pad_to(b, 1, bn)
        gn = b.shape[1] // bn
        grid = (T, gm, gn, nk)
        if a_is_compact:
            a_spec = pl.BlockSpec((1, bm, bs), lambda t, i, j, k, ids: (t, i, k))
        else:
            a_spec = pl.BlockSpec((1, bm, bs),
                                  lambda t, i, j, k, ids: (t, i, ids[t, k]))
        b_spec = pl.BlockSpec((bs, bn), lambda t, i, j, k, ids: (ids[t, k], j))
        o_spec = pl.BlockSpec((1, bm, bn), lambda t, i, j, k, ids: (t, i, j))
        out_shape = jax.ShapeDtypeStruct((T, a.shape[1], b.shape[1]), a.dtype)
        acc = pltpu.VMEM((bm, bn), jnp.float32)
        n_k = nk
        out_slice = (slice(None), slice(0, M), slice(0, N))
    else:
        # y (T, M, nk*bs) = a (T, M, N) @ b[kept_t, :].T; contract over N.
        N = a.shape[2]
        bk = bk or min(128, N)
        a = _pad_to(a, 2, bk)
        b = _pad_to(b, 1, bk)
        gk = a.shape[2] // bk
        grid = (T, gm, nk, gk)
        a_spec = pl.BlockSpec((1, bm, bk), lambda t, i, j, k, ids: (t, i, k))
        b_spec = pl.BlockSpec((bs, bk), lambda t, i, j, k, ids: (ids[t, j], k))
        o_spec = pl.BlockSpec((1, bm, bs), lambda t, i, j, k, ids: (t, i, j))
        out_shape = jax.ShapeDtypeStruct((T, a.shape[1], nk * bs), a.dtype)
        acc = pltpu.VMEM((bm, bs), jnp.float32)
        n_k = gk
        out_slice = (slice(None), slice(0, M), slice(None))

    kernel = functools.partial(_mm_kernel_stepped, n_k=n_k,
                               transpose_b=transpose_b)
    y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[a_spec, b_spec],
            out_specs=o_spec,
            scratch_shapes=[acc],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(keep_blocks, a, b)
    return y[out_slice]
