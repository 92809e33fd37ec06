"""Pallas TPU flash attention (fwd + bwd), GQA-aware, causal/windowed.

The roofline analysis (EXPERIMENTS §Roofline) shows every train/prefill
cell memory-bound on attention intermediates: the XLA-level chunked
attention materializes (cq x ckv) fp32 score tiles through HBM in fwd AND
bwd. This kernel keeps the tiles VMEM-resident (classic flash): HBM traffic
drops from O(S^2) scores to O(S·d) operands — the single largest §Perf
lever, applied beyond the paper.

Layout: q (B, Hq, Sq, d), k/v (B, Hkv, Sk, d); grid (B*Hq, nq, nk) with the
kv loop innermost; fp32 running (m, l, acc) scratch across the kv loop.
Causal/window masking from absolute positions; GQA by indexing kv head
hq // group in the BlockSpec index_map (no materialized repeat).

Backward: standard two-pass flash bwd — dq in one pallas_call (kv inner),
dk/dv in another (q inner) — recomputing p from (q, k, delta=rowsum(do*o),
lse) so nothing quadratic is ever stored. Validated in interpret mode
against the pure-jnp oracle (tests/test_flash.py).
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _mask(qpos, kpos, causal, window):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), jnp.bool_)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, window, bq, bk, nk):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    i = pl.program_id(1)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)[:, 0]

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(qpos, kpos, causal, window), s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = (acc_scr[...] * alpha
                    + jax.lax.dot_general(
                        p.astype(v_ref.dtype), v_ref[...],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l))[:, 0]


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "window", "bq", "bk",
                              "interpret"))
def _flash_fwd(q, k, v, *, scale, causal, window, bq, bk, interpret):
    B, Hq, Sq, d = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    nq, nk = Sq // bq, Sk // bk
    grid = (B * Hq, nq, nk)

    qs = pl.BlockSpec((1, 1, bq, d), lambda h, i, j: (h // Hq, h % Hq, i, 0))
    ks = pl.BlockSpec((1, 1, bk, d),
                      lambda h, i, j: (h // Hq, (h % Hq) // G, j, 0))
    os = pl.BlockSpec((1, 1, bq, d), lambda h, i, j: (h // Hq, h % Hq, i, 0))
    ls = pl.BlockSpec((1, 1, bq), lambda h, i, j: (h // Hq, h % Hq, i))

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
        _fwd_kernel(q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0],
                    o_ref.at[0, 0], lse_ref.at[0, 0], m_scr, l_scr, acc_scr,
                    scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                    nk=nk)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qs, ks, ks],
        out_specs=[os, ls],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Sq, d), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, Sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, causal, window, bq, bk, nk):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    i = pl.program_id(1)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)[:, 0]

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(qpos, kpos, causal, window), s, NEG_INF)
    p = jnp.exp(s - lse_ref[...][:, None])
    do = do_ref[...].astype(jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[...].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[...][:, None]) * scale
    acc_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _flush():
        dq_ref[...] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, window, bq, bk, nq):
    i = pl.program_id(2)          # q loop innermost

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    j = pl.program_id(1)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)[:, 0]

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(qpos, kpos, causal, window), s, NEG_INF)
    p = jnp.exp(s - lse_ref[...][:, None])            # (bq, bk)
    do = do_ref[...].astype(jnp.float32)
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[...].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[...][:, None]) * scale
    dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _flush():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "window", "bq", "bk",
                              "interpret"))
def _flash_bwd(q, k, v, o, lse, do, *, scale, causal, window, bq, bk,
               interpret):
    B, Hq, Sq, d = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    nq, nk = Sq // bq, Sk // bk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                      # (B, Hq, Sq)

    qs = pl.BlockSpec((1, 1, bq, d), lambda h, i, j: (h // Hq, h % Hq, i, 0))
    ks = pl.BlockSpec((1, 1, bk, d),
                      lambda h, i, j: (h // Hq, (h % Hq) // G, j, 0))
    ls = pl.BlockSpec((1, 1, bq), lambda h, i, j: (h // Hq, h % Hq, i))

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  acc):
        _dq_kernel(q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0],
                   do_ref.at[0, 0], lse_ref.at[0, 0], delta_ref.at[0, 0],
                   dq_ref.at[0, 0], acc, scale=scale, causal=causal,
                   window=window, bq=bq, bk=bk, nk=nk)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[qs, ks, ks, qs, ls, ls],
        out_specs=qs,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv: one pass per Q-HEAD (GQA heads accumulate via sum over group).
    qs2 = pl.BlockSpec((1, 1, bq, d), lambda h, j, i: (h // Hq, h % Hq, i, 0))
    ks2 = pl.BlockSpec((1, 1, bk, d),
                       lambda h, j, i: (h // Hq, (h % Hq) // G, j, 0))
    kqs2 = pl.BlockSpec((1, 1, bk, d), lambda h, j, i: (h // Hq, h % Hq, j, 0))
    ls2 = pl.BlockSpec((1, 1, bq), lambda h, j, i: (h // Hq, h % Hq, i))

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dks, dvs):
        _dkv_kernel(q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0],
                    do_ref.at[0, 0], lse_ref.at[0, 0], delta_ref.at[0, 0],
                    dk_ref.at[0, 0], dv_ref.at[0, 0], dks, dvs,
                    scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                    nq=nq)

    dk_h, dv_h = pl.pallas_call(
        dkv_kernel,
        grid=(B * Hq, nk, nq),
        in_specs=[qs2, ks2, ks2, qs2, ls2, ls2],
        out_specs=[kqs2, kqs2],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Sk, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hq, Sk, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(B, Hkv, G, Sk, d).sum(2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, G, Sk, d).sum(2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=None, bq=512, bk=512,
                    interpret=None):
    """q: (B, Sq, Hq, d); k, v: (B, Sk, Hkv, d) -> (B, Sq, Hq, d).

    GQA handled by head-index mapping (no kv repeat). Sliding-window
    masking supported (FLOPs of masked tiles are still executed; the
    wall-clock win on TPU comes from HBM traffic, not mask sparsity —
    the windowed XLA path already handles the FLOP side)."""
    o, _ = _fa_fwd_res(q, k, v, causal, window, bq, bk, interpret)
    return o


def _resolve(q, bq, bk, Sq, Sk, interpret):
    interpret = resolve_interpret(interpret)
    bq = min(bq, Sq)
    while Sq % bq:
        bq -= 1
    bk = min(bk, Sk)
    while Sk % bk:
        bk -= 1
    return bq, bk, interpret


def _fa_fwd_res(q, k, v, causal, window, bq, bk, interpret):
    B, Sq, Hq, d = q.shape
    Sk = k.shape[1]
    bq, bk, interpret = _resolve(q, bq, bk, Sq, Sk, interpret)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o, lse = _flash_fwd(qt, kt, vt, scale=d ** -0.5, causal=causal,
                        window=window, bq=bq, bk=bk, interpret=interpret)
    return o.transpose(0, 2, 1, 3), (q, k, v, o, lse)


def _fa_fwd(q, k, v, causal, window, bq, bk, interpret):
    o, res = _fa_fwd_res(q, k, v, causal, window, bq, bk, interpret)
    return o, res


def _fa_bwd(causal, window, bq, bk, interpret, res, do):
    q, k, v, o_t, lse = res
    B, Sq, Hq, d = q.shape
    Sk = k.shape[1]
    bq, bk, interpret = _resolve(q, bq, bk, Sq, Sk, interpret)
    dq, dk, dv = _flash_bwd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), o_t, lse, do.transpose(0, 2, 1, 3),
        scale=d ** -0.5, causal=causal, window=window, bq=bq, bk=bk,
        interpret=interpret)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


flash_attention.defvjp(_fa_fwd, _fa_bwd)
