"""Fused two-pass seq2seq decoder recurrence (the NMT engine="fused" core).

Luong input feeding makes the decoder's step-t NR input ``[embed_t ;
h~_{t-1}]`` depend on step t-1's attention output, which is why the decoder
used to keep its whole NR matmul in-scan. The equivalence-preserving
restructure implemented here splits that joint matmul:

    [embed_t ; h~_{t-1}] @ W  ==  embed_t @ W_x  +  h~_{t-1} @ W_feed

The ``embed_t @ W_x`` half has NO sequential dependence — it hoists out of
the scan and runs time-batched through ``dense_sdrop_scheduled`` (Phase A,
(1-p) FLOPs, bias folded in) exactly like every other NR matmul. Only the
feed half stays recurrent, and it is carried through this module's fused
scan as one more recurrent matmul next to ``h_{t-1} @ U`` — gathered
compact off its own keep-block schedule, so the in-scan FLOPs are (1-p)
too. Attention itself cannot leave the forward scan (h~_{t-1} -> gates_t ->
h_t -> attention_t -> h~_t is a nonlinear chain), so each step's Luong
general attention + h~ readout runs inside the pass and the h~ sequence is
emitted for the time-batched pass 2 (output dropout + vocab projection) —
the attention residuals (alpha rows) double as the backward's softmax
state, which the hand-derived reverse pass would need even if attention
were recomputed batched.

Per decoder step t (nl stacked LSTM layers, states (h_l, c_l), feed h~):

    gates_0 = gx0_t + drop(h~_{t-1}) @ W_feed + drop(h_{0,t-1}) @ U_0
    gates_l = drop(h_{l-1,t}) @ W_l + b_l + drop(h_{l,t-1}) @ U_l   (l >= 1)
    h_l, c_l = lstm_pointwise(gates_l, c_l)
    scores   = h_top @ enc_proj^T + score_bias        (additive -1e30 mask)
    alpha    = softmax(scores);  ctx = alpha @ enc_out
    h~_t     = tanh([ctx ; h_top] @ w_comb)

Every in-scan dropout site has hidden-width H. Canonical site order (the
``sites`` argument, 2*nl entries):

    [ feed, rh_0 .. rh_{nl-1}, nr_1 .. nr_{nl-1} ]

each ``(keep_blocks (rows, nk) | None, dense_mask (rows, B, H) | None,
block_size, scale)`` with rows in {1, T} (1 = FIXED, one mask reused every
step — Case II/IV).

The backward is hand-derived and fused the same way ``cell_scan.py``'s is:
one reverse-time pass carrying (dh_l, dc_l, dfeed) with all weight grads
accumulated along the way — structured sites keep BP/WG compact (gather /
scatter-add on kept blocks only, FIXED keeps dU compact until one final
scatter), the attention backward re-derives dscores through the softmax
jacobian from the stored alpha rows, and dgx0 flows back into Phase A's
autodiff (dW_x, db, dembed). ``impl="xla"`` is the CPU production path
(hand-written ``lax.scan``s); ``impl="pallas"`` runs both directions as
single time-as-grid persistent kernels (state in VMEM scratch, weights +
encoder memory resident via constant index maps, ids tables scalar-
prefetched) and auto-falls back to interpret mode off TPU.

**Ragged batches** (PR 8): an optional per-row ``lengths (B,) int32``
rides as one more scalar-prefetch operand (appended after the 2*nl ids
tables, so ``num_scalar_prefetch = 2*nl + 1``; a (1,) dummy when
rectangular and the ``ragged`` flag compiles the predicate away). Forward:
step t of row b with ``t >= lengths[b]`` writes the t-1 carries (h_l, c_l,
feed) through unchanged, so the emitted h~ repeats the last valid readout
and the finals are the state at the last real step — which is what the
serving prefill handoff consumes. Backward: frozen steps zero the (dh, dc,
dh~) cotangents INTO the step math (pointwise + attention backward are
linear in them, so every weight/attention grad contribution vanishes) and
pass the original cotangents straight through to t-1. A token-packed
batch therefore produces bit-for-bit the loss and grads of running each
row unpacked at its own length (tests/test_ragged.py).

Dtype contract: all step math runs in f32 inside the scan regardless of
operand dtypes; residual sequences (gates, h, c, h~, alpha) are stored
f32 by the pallas path; the returned h~ sequence / feed final carry
``gx0.dtype`` and the h/c finals carry ``h0.dtype``/``c0.dtype``;
cotangents are cast back to each primal's dtype on the way out.

Oracle: every (impl, engine) combination is tested against
``kernels/ref.py::decoder_scan_ref`` — a plain ``jax.lax.scan``
transliteration of the step equations above differentiated by autodiff —
in tests/test_kernels.py and tests/test_engine.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret, scan_compiler_params
from repro.kernels.cell_scan import (_block_ds, _block_rows, _dot_nt,
                                     _dot_tn, _dummy_ids, _dummy_lens,
                                     _float0_like, _is_fixed, _pad_blocks,
                                     _padded_block, _rh_mode, _stage_blocks,
                                     _unit_ids_table, _unpad_blocks,
                                     _unstage_blocks)
from repro.kernels.lstm_scan import _pointwise_bwd, _pointwise_fwd

F32 = jnp.float32


def _pw_fwd(gates, c_prev):
    h, (c,) = _pointwise_fwd(gates, (c_prev,), forget_bias=0.0)
    return h, c


def _pw_bwd(gates, c_prev, c_new, dh, dc):
    dgates, (dc_prev,) = _pointwise_bwd(gates, (c_prev,), (c_new,), dh,
                                        (dc,), forget_bias=0.0)
    return dgates, dc_prev


@dataclasses.dataclass(frozen=True)
class SiteDesc:
    """Static per-site dropout descriptor (hashable: jit/custom_vjp key)."""
    mode: str          # "structured" | "dense" | "off"
    fixed: bool        # one mask row reused for all T steps
    block_size: int
    scale: float
    nk: int            # kept blocks per row (structured only)


def _mk_site(kb, mask, block_size, scale):
    mode = _rh_mode(kb, mask)
    fixed = _is_fixed(mode, kb, mask)
    nk = kb.shape[1] if mode == "structured" else 0
    desc = SiteDesc(mode, fixed, int(block_size), float(scale), nk)
    return desc, (kb if mode == "structured" else mask)


def _site_weights(nl, ops):
    """Canonical site index -> the weight it drops into.

    0 -> w_feed, 1+l -> us[l] (l in [0, nl)), nl+l -> ws[l-1] (l in [1, nl)).
    """
    return [ops["w_feed"]] + list(ops["us"]) + list(ops["ws"])


# ---------------------------------------------------------------------------
# XLA impl: hand-written forward/reverse lax.scans (CPU production path).
# Same compact-gather / FIXED-hoist structure as cell_scan's _xla_fwd/_bwd,
# generalized to 2*nl sites + the in-scan attention (and its backward).
# ---------------------------------------------------------------------------


def _site_tables(descs, masks):
    """Per-site (unit-ids table, hoisted FIXED compact weight slot, xs)."""
    uids = [None] * len(descs)
    xs = [None] * len(descs)
    for i, d in enumerate(descs):
        if d.mode == "structured":
            uids[i] = _unit_ids_table(masks[i], d.block_size)
            if not d.fixed:
                xs[i] = uids[i]
        elif d.mode == "dense" and not d.fixed:
            xs[i] = masks[i]
    return uids, tuple(xs)


def _xla_fwd(nl, descs, ops, masks, lengths):
    gx0 = ops["gx0"]
    ws = _site_weights(nl, ops)
    uids, xs_extra = _site_tables(descs, masks)
    wc0 = [jnp.take(ws[i], uids[i][0], axis=0)
           if d.mode == "structured" and d.fixed else None
           for i, d in enumerate(descs)]
    ep = ops["enc_proj"].astype(F32)
    eo = ops["enc_out"].astype(F32)
    sb = ops["score_bias"].astype(F32)
    wcomb = ops["w_comb"].astype(F32)
    bs_l = [b.astype(F32) for b in ops["bs"]]

    def mm(x, i, extra):
        d = descs[i]
        if d.mode == "off":
            return jnp.dot(x, ws[i], preferred_element_type=F32)
        if d.mode == "structured":
            ids_t = uids[i][0] if d.fixed else extra
            w_c = wc0[i] if d.fixed else jnp.take(ws[i], ids_t, axis=0)
            return jnp.dot(jnp.take(x, ids_t, axis=-1), w_c,
                           preferred_element_type=F32) * d.scale
        m_t = masks[i][0] if d.fixed else extra
        return jnp.dot(x * m_t.astype(F32) * d.scale, ws[i],
                       preferred_element_type=F32)

    ts = jnp.arange(gx0.shape[0]) if lengths is not None else None

    def step(carry, xs):
        hs, cs, feed = carry
        gx0_t, extras, t = xs
        g = gx0_t.astype(F32) + mm(feed, 0, extras[0]) + mm(hs[0], 1,
                                                            extras[1])
        h, c = _pw_fwd(g, cs[0])
        gates, new_h, new_c = [g], [h], [c]
        cur = h
        for l in range(1, nl):
            g = (mm(cur, nl + l, extras[nl + l]) + bs_l[l - 1]
                 + mm(hs[l], 1 + l, extras[1 + l]))
            h, c = _pw_fwd(g, cs[l])
            gates.append(g)
            new_h.append(h)
            new_c.append(c)
            cur = h
        scores = jnp.einsum("bh,bsh->bs", cur, ep,
                            preferred_element_type=F32) + sb
        alpha = jax.nn.softmax(scores, axis=-1)
        ctxv = jnp.einsum("bs,bsh->bh", alpha, eo,
                          preferred_element_type=F32)
        htil = jnp.tanh(jnp.dot(jnp.concatenate([ctxv, cur], -1), wcomb,
                                preferred_element_type=F32))
        if lengths is not None:
            # rows past their length freeze every carry (h, c, feed)
            act = (t < lengths)[:, None]
            new_h = [jnp.where(act, v, p) for v, p in zip(new_h, hs)]
            new_c = [jnp.where(act, v, p) for v, p in zip(new_c, cs)]
            htil = jnp.where(act, htil, feed)
        return ((tuple(new_h), tuple(new_c), htil),
                (htil, tuple(gates), tuple(new_h), tuple(new_c), alpha))

    init = (tuple(ops["h0"][l].astype(F32) for l in range(nl)),
            tuple(ops["c0"][l].astype(F32) for l in range(nl)),
            ops["feed0"].astype(F32))
    (hF, cF, feedF), ys = jax.lax.scan(step, init, (gx0, xs_extra, ts))
    htil_seq, gates_seqs, h_seqs, c_seqs, alpha_seq = ys
    return (htil_seq, gates_seqs, h_seqs, c_seqs, alpha_seq,
            (jnp.stack(hF), jnp.stack(cF), feedF))


def _xla_bwd(nl, descs, ops, masks, lengths, res, dout):
    gates_seqs, h_seqs, c_seqs, htil_seq, alpha_seq = res
    d_htil, d_hfin, d_cfin, d_ffin = dout
    T, B, G = ops["gx0"].shape
    H = ops["w_feed"].shape[0]
    ws = _site_weights(nl, ops)
    uids, xs_extra = _site_tables(descs, masks)
    wc0 = [jnp.take(ws[i], uids[i][0], axis=0)
           if d.mode == "structured" and d.fixed else None
           for i, d in enumerate(descs)]
    ep = ops["enc_proj"].astype(F32)
    eo = ops["enc_out"].astype(F32)
    wcomb = ops["w_comb"].astype(F32)

    h_prev_seqs = tuple(
        jnp.concatenate([ops["h0"][l][None].astype(F32), h_seqs[l][:-1]])
        for l in range(nl))
    c_prev_seqs = tuple(
        jnp.concatenate([ops["c0"][l][None].astype(F32), c_seqs[l][:-1]])
        for l in range(nl))
    feed_prev_seq = jnp.concatenate(
        [ops["feed0"][None].astype(F32), htil_seq[:-1]])

    def bp(dg, i, extra):
        """Input grad through site i: masked (compact where structured)."""
        d = descs[i]
        if d.mode == "off":
            return jnp.dot(dg, ws[i].T, preferred_element_type=F32)
        if d.mode == "structured":
            ids_t = uids[i][0] if d.fixed else extra
            w_c = wc0[i] if d.fixed else jnp.take(ws[i], ids_t, axis=0)
            dx_c = jnp.dot(dg, w_c.T, preferred_element_type=F32) * d.scale
            return jnp.zeros((B, H), F32).at[:, ids_t].set(dx_c)
        m_t = masks[i][0] if d.fixed else extra
        return (jnp.dot(dg, ws[i].T, preferred_element_type=F32)
                * m_t.astype(F32) * d.scale)

    def wg_init(i):
        d = descs[i]
        if d.mode == "structured" and d.fixed:
            return jnp.zeros((uids[i].shape[1], G), F32)   # compact rows
        return jnp.zeros((H, G), F32)

    def wg_add(acc, x, dg, i, extra):
        d = descs[i]
        if d.mode == "off":
            return acc + jnp.einsum("bh,bg->hg", x, dg,
                                    preferred_element_type=F32)
        if d.mode == "structured":
            ids_t = uids[i][0] if d.fixed else extra
            contrib = jnp.einsum("bk,bg->kg", jnp.take(x, ids_t, axis=-1),
                                 dg, preferred_element_type=F32) * d.scale
            return acc + contrib if d.fixed else acc.at[ids_t].add(contrib)
        m_t = masks[i][0] if d.fixed else extra
        return acc + jnp.einsum("bh,bg->hg", x * m_t.astype(F32) * d.scale,
                                dg, preferred_element_type=F32)

    def wg_fin(acc, i):
        d = descs[i]
        if d.mode == "structured" and d.fixed:
            return jnp.zeros((H, G), F32).at[uids[i][0]].set(acc)
        return acc

    ts = jnp.arange(T) if lengths is not None else None

    def step(carry, xs):
        dh, dc, dfeed, accs, dbs, dwcomb, dep, deo = carry
        (dy_t, g_t, h_t, hp_t, c_t, cp_t, htil_t, fp_t, alpha_t,
         extras, t) = xs
        # h~ readout backward (tanh + w_comb + attention softmax jacobian)
        dhtil = dy_t.astype(F32) + dfeed
        if lengths is not None:
            # frozen rows: zero the cotangents into the step math (every
            # piece below is linear in them, so all weight/attention grads
            # vanish) and pass the originals through to t-1 at the end.
            act = (t < lengths)[:, None]
            dhtil_c = jnp.where(act, dhtil, 0.0)
        else:
            act, dhtil_c = None, dhtil
        dpre = dhtil_c * (1.0 - htil_t * htil_t)
        cur = h_t[nl - 1]
        ctxv = jnp.einsum("bs,bsh->bh", alpha_t, eo,
                          preferred_element_type=F32)
        dwcomb = dwcomb + jnp.einsum(
            "bi,bh->ih", jnp.concatenate([ctxv, cur], -1), dpre,
            preferred_element_type=F32)
        dcat = jnp.dot(dpre, wcomb.T, preferred_element_type=F32)
        dctx, dcur = dcat[:, :H], dcat[:, H:]
        dalpha = jnp.einsum("bh,bsh->bs", dctx, eo,
                            preferred_element_type=F32)
        deo = deo + jnp.einsum("bs,bh->bsh", alpha_t, dctx,
                               preferred_element_type=F32)
        dscores = alpha_t * (dalpha - jnp.sum(alpha_t * dalpha, -1,
                                              keepdims=True))
        dcur = dcur + jnp.einsum("bs,bsh->bh", dscores, ep,
                                 preferred_element_type=F32)
        dep = dep + jnp.einsum("bs,bh->bsh", dscores, cur,
                               preferred_element_type=F32)
        # LSTM stack backward, top layer down; NR input grads flow into the
        # SAME step's lower layer, RH/feed grads into the carry (t-1).
        dh_cur = list(dh)
        dh_cur[nl - 1] = dh_cur[nl - 1] + dcur
        new_dh, new_dc = [None] * nl, [None] * nl
        accs, dbs = list(accs), list(dbs)
        dgx0_t = None
        new_dfeed = None
        for l in reversed(range(nl)):
            if lengths is not None:
                dh_cell = jnp.where(act, dh_cur[l], 0.0)
                dc_cell = jnp.where(act, dc[l], 0.0)
            else:
                dh_cell, dc_cell = dh_cur[l], dc[l]
            dg, dc_prev = _pw_bwd(g_t[l], cp_t[l], c_t[l], dh_cell, dc_cell)
            new_dh[l] = bp(dg, 1 + l, extras[1 + l])
            accs[1 + l] = wg_add(accs[1 + l], hp_t[l], dg, 1 + l,
                                 extras[1 + l])
            new_dc[l] = dc_prev
            if lengths is not None:
                new_dh[l] = new_dh[l] + jnp.where(act, 0.0, dh_cur[l])
                new_dc[l] = new_dc[l] + jnp.where(act, 0.0, dc[l])
            if l > 0:
                dh_cur[l - 1] = dh_cur[l - 1] + bp(dg, nl + l,
                                                   extras[nl + l])
                accs[nl + l] = wg_add(accs[nl + l], h_t[l - 1], dg, nl + l,
                                      extras[nl + l])
                dbs[l - 1] = dbs[l - 1] + dg.sum(axis=0)
            else:
                dgx0_t = dg
                new_dfeed = bp(dg, 0, extras[0])
                if lengths is not None:
                    new_dfeed = new_dfeed + jnp.where(act, 0.0, dhtil)
                accs[0] = wg_add(accs[0], fp_t, dg, 0, extras[0])
        return ((tuple(new_dh), tuple(new_dc), new_dfeed, tuple(accs),
                 tuple(dbs), dwcomb, dep, deo), dgx0_t)

    init = (tuple(d_hfin[l].astype(F32) for l in range(nl)),
            tuple(d_cfin[l].astype(F32) for l in range(nl)),
            d_ffin.astype(F32),
            tuple(wg_init(i) for i in range(2 * nl)),
            tuple(jnp.zeros((G,), F32) for _ in range(nl - 1)),
            jnp.zeros((2 * H, H), F32),
            jnp.zeros(ep.shape, F32), jnp.zeros(eo.shape, F32))
    (dh0, dc0, dfeed0, accs, dbs, dwcomb, dep, deo), dgx = jax.lax.scan(
        step, init,
        (d_htil, gates_seqs, h_seqs, h_prev_seqs, c_seqs, c_prev_seqs,
         htil_seq, feed_prev_seq, alpha_seq, xs_extra, ts),
        reverse=True)
    accs = [wg_fin(a, i) for i, a in enumerate(accs)]
    return (dgx, accs, dbs, dwcomb, dep, deo,
            jnp.stack(dh0), jnp.stack(dc0), dfeed0)


# ---------------------------------------------------------------------------
# Pallas impl: one time-as-grid kernel per direction. Refs are variadic in
# nl and unpacked by position: [scalar ids x 2nl | inputs | outputs |
# scratch]. Weights + encoder memory stay resident (constant index maps);
# (h, c, feed) carries and every grad accumulator live in f32 VMEM scratch.
# ---------------------------------------------------------------------------


def _m3_inputs(mask, dtype, fixed, rev=None):
    """(m_in, m_spec) for a (1, B, H) per-step site-mask ref."""
    if mask is None:
        m_in = jnp.zeros((1, 1, 1), dtype)               # unused placeholder
        return m_in, pl.BlockSpec((1, 1, 1), lambda t, *_: (0, 0, 0))
    per_t = rev if rev is not None else (lambda t, *_: (t, 0, 0))
    spec = pl.BlockSpec((1, *mask.shape[1:]),
                        (lambda t, *_: (0, 0, 0)) if fixed else per_t)
    return mask, spec


def _pl_mm(x, w_ref, ids_ref, m_ref, t, d, padded, stage):
    """drop(x) @ w in f32 inside the kernel (compact when structured)."""
    if d.mode == "off":
        return jnp.dot(x, w_ref[...].astype(F32), preferred_element_type=F32)
    if d.mode == "structured":
        bs = d.block_size
        _stage_blocks(x, stage, bs, padded)
        acc = jnp.zeros((x.shape[0], w_ref.shape[-1]), F32)
        for k in range(d.nk):                   # static unroll: exact-k masks
            bid = ids_ref[0 if d.fixed else t, k]
            xb = _block_rows(stage, bid, bs, padded)              # (bs, B)
            wb = _block_rows(w_ref, bid, bs, padded).astype(F32)  # (bs, G)
            acc += _dot_tn(xb, wb)
        return acc * d.scale
    m = m_ref[0].astype(F32)
    return jnp.dot(x * m * d.scale, w_ref[...].astype(F32),
                   preferred_element_type=F32)


def _pl_scores(x, mem):
    """(B, H) . (B, S, H) -> (B, S), as the batched matmul Mosaic lowers
    (a rank-2 operand with no free axis does not)."""
    return jnp.einsum("bqh,bsh->bqs", x[:, None], mem,
                      preferred_element_type=F32)[:, 0]


def _pl_context(a, mem):
    """(B, S) . (B, S, H) -> (B, H), likewise."""
    return jnp.einsum("bqs,bsh->bqh", a[:, None], mem,
                      preferred_element_type=F32)[:, 0]


def _pl_fwd_kernel(*args, nl, descs, paddeds, n_steps, ragged):
    ns = 2 * nl
    i = 0
    ids_refs = args[i:i + ns]; i += ns                              # noqa: E702
    lens_ref = args[i]; i += 1                                      # noqa: E702
    gx0 = args[i]; i += 1                                           # noqa: E702
    us = args[i:i + nl]; i += nl                                    # noqa: E702
    ws = args[i:i + nl - 1]; i += nl - 1                            # noqa: E702
    bs_l = args[i:i + nl - 1]; i += nl - 1                          # noqa: E702
    w_feed, w_comb, ep, eo, sb = args[i:i + 5]; i += 5              # noqa: E702
    h0, c0, f0 = args[i:i + 3]; i += 3                              # noqa: E702
    m_refs = args[i:i + ns]; i += ns                                # noqa: E702
    htil_r, alpha_r = args[i:i + 2]; i += 2                         # noqa: E702
    gates_rs = args[i:i + nl]; i += nl                              # noqa: E702
    h_rs = args[i:i + nl]; i += nl                                  # noqa: E702
    c_rs = args[i:i + nl]; i += nl                                  # noqa: E702
    hfin_r, cfin_r, ffin_r = args[i:i + 3]; i += 3                  # noqa: E702
    h_s, c_s, feed_s = args[i:i + 3]; i += 3                        # noqa: E702
    stage = args[i] if i < len(args) else None
    site_w = [w_feed] + list(us) + list(ws)

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[...] = h0[...].astype(F32)
        c_s[...] = c0[...].astype(F32)
        feed_s[...] = f0[...].astype(F32)

    def mm(x, i, extra_t):
        return _pl_mm(x, site_w[i], ids_refs[i], m_refs[i], extra_t,
                      descs[i], paddeds[i], stage)

    g = (gx0[0].astype(F32) + mm(feed_s[...], 0, t) + mm(h_s[0], 1, t))
    h, c = _pw_fwd(g, c_s[0])
    gates, new_h, new_c = [g], [h], [c]
    cur = h
    for l in range(1, nl):
        g = (mm(cur, nl + l, t) + bs_l[l - 1][0].astype(F32)
             + mm(h_s[l], 1 + l, t))
        h, c = _pw_fwd(g, c_s[l])
        gates.append(g)
        new_h.append(h)
        new_c.append(c)
        cur = h
    H = cur.shape[-1]
    scores = _pl_scores(cur, ep[...].astype(F32)) + sb[...].astype(F32)
    alpha = jax.nn.softmax(scores, axis=-1)
    ctxv = _pl_context(alpha, eo[...].astype(F32))
    wc = w_comb[...].astype(F32)
    htil = jnp.tanh(jnp.dot(ctxv, wc[:H], preferred_element_type=F32)
                    + jnp.dot(cur, wc[H:], preferred_element_type=F32))

    if ragged:
        # rows past their length freeze every carry (h, c, feed)
        act = (t < lens_ref[...])[:, None]
        new_h = [jnp.where(act, v, h_s[l]) for l, v in enumerate(new_h)]
        new_c = [jnp.where(act, v, c_s[l]) for l, v in enumerate(new_c)]
        htil = jnp.where(act, htil, feed_s[...])

    for l in range(nl):
        h_s[l] = new_h[l]
        c_s[l] = new_c[l]
        gates_rs[l][0] = gates[l].astype(gates_rs[l].dtype)
        h_rs[l][0] = new_h[l].astype(h_rs[l].dtype)
        c_rs[l][0] = new_c[l].astype(c_rs[l].dtype)
    feed_s[...] = htil
    htil_r[0] = htil.astype(htil_r.dtype)
    alpha_r[0] = alpha.astype(alpha_r.dtype)

    @pl.when(t == n_steps - 1)
    def _flush():
        hfin_r[...] = jnp.stack(new_h).astype(hfin_r.dtype)
        cfin_r[...] = jnp.stack(new_c).astype(cfin_r.dtype)
        ffin_r[...] = htil.astype(ffin_r.dtype)


def _site_layout(nl, descs, ops, batch):
    """Site weights as the kernels read them + their keep-block layout.

    Structured sites get their weight's keep-blocks padded to the row tile
    (cell_scan's "Keep-block row layout"); ``stage`` is the (rows, B) f32
    scratch the sites stage their input in, one at a time, sized for the
    widest (empty when no site is structured). Returns ``(w_feed, us, ws,
    paddeds, stage)``.
    """
    ws = _site_weights(nl, ops)
    paddeds = tuple(_padded_block(d.block_size, w.dtype)
                    if d.mode == "structured" else d.block_size
                    for d, w in zip(descs, ws))
    ws = [_pad_blocks(w, d.block_size, p) if d.mode == "structured" else w
          for d, w, p in zip(descs, ws, paddeds)]
    rows = [w.shape[0] for d, w in zip(descs, ws) if d.mode == "structured"]
    stage = [pltpu.VMEM((max(rows), batch), F32)] if rows else []
    return ws[0], ws[1:1 + nl], ws[1 + nl:], paddeds, stage


def _pallas_fwd(nl, descs, ops, masks, lengths, *, interpret):
    gx0 = ops["gx0"]
    T, B, G = gx0.shape
    H = ops["w_feed"].shape[0]
    S = ops["enc_out"].shape[1]
    ns = 2 * nl
    ragged = lengths is not None
    ids = [masks[i] if d.mode == "structured" else _dummy_ids()
           for i, d in enumerate(descs)]
    lens = lengths.astype(jnp.int32) if ragged else _dummy_lens()
    m_ins, m_specs = [], []
    for i, d in enumerate(descs):
        m_in, m_spec = _m3_inputs(masks[i] if d.mode == "dense" else None,
                                  F32, d.fixed)
        m_ins.append(m_in)
        m_specs.append(m_spec)

    w_feed, us, ws, paddeds, stage = _site_layout(nl, descs, ops, B)
    seq = lambda shp: pl.BlockSpec((1, *shp), lambda t, *_: (t,) + (0,) * len(shp))
    const = lambda shp: pl.BlockSpec(shp, lambda t, *_: (0,) * len(shp))

    kernel = functools.partial(_pl_fwd_kernel, nl=nl, descs=descs,
                               paddeds=paddeds, n_steps=T, ragged=ragged)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=ns + 1,
            grid=(T,),
            in_specs=[
                seq((B, G)),                                   # gx0
                *[const(u.shape) for u in us],                 # U_l
                *[const(w.shape) for w in ws],                 # W_l
                *([const((1, G))] * (nl - 1)),                 # b_l
                const(w_feed.shape), const((2 * H, H)),        # w_feed/w_comb
                const((B, S, H)), const((B, S, H)),            # enc mem
                const((B, S)),                                 # score_bias
                const((nl, B, H)), const((nl, B, H)),          # h0/c0
                const((B, H)),                                 # feed0
                *m_specs,
            ],
            out_specs=[
                seq((B, H)), seq((B, S)),                      # htil/alpha
                *([seq((B, G))] * nl),                         # gates_l
                *([seq((B, H))] * nl), *([seq((B, H))] * nl),  # h_l/c_l
                const((nl, B, H)), const((nl, B, H)),          # finals
                const((B, H)),
            ],
            scratch_shapes=[pltpu.VMEM((nl, B, H), F32),
                            pltpu.VMEM((nl, B, H), F32),
                            pltpu.VMEM((B, H), F32)] + stage,
        ),
        out_shape=[jax.ShapeDtypeStruct((T, B, H), F32),
                   jax.ShapeDtypeStruct((T, B, S), F32),
                   *[jax.ShapeDtypeStruct((T, B, G), F32)] * nl,
                   *[jax.ShapeDtypeStruct((T, B, H), F32)] * (2 * nl),
                   jax.ShapeDtypeStruct((nl, B, H), F32),
                   jax.ShapeDtypeStruct((nl, B, H), F32),
                   jax.ShapeDtypeStruct((B, H), F32)],
        compiler_params=scan_compiler_params(),
        interpret=interpret,
    )(*ids, lens, gx0, *us, *ws,
      *[b.reshape(1, G) for b in ops["bs"]],
      w_feed, ops["w_comb"], ops["enc_proj"], ops["enc_out"],
      ops["score_bias"], ops["h0"], ops["c0"], ops["feed0"], *m_ins)
    htil_seq, alpha_seq = outs[0], outs[1]
    gates_seqs = tuple(outs[2:2 + nl])
    h_seqs = tuple(outs[2 + nl:2 + 2 * nl])
    c_seqs = tuple(outs[2 + 2 * nl:2 + 3 * nl])
    finals = (outs[2 + 3 * nl], outs[3 + 3 * nl], outs[4 + 3 * nl])
    return htil_seq, gates_seqs, h_seqs, c_seqs, alpha_seq, finals


def _pl_bp(dg, w_ref, ids_ref, m_ref, r, d, H, padded, dstage):
    """Input grad through a site, inside the kernel (masked/compact)."""
    if d.mode == "off":
        return jnp.dot(dg, w_ref[...].astype(F32).T,
                       preferred_element_type=F32)
    if d.mode == "structured":
        bs = d.block_size
        dstage[...] = jnp.zeros(dstage.shape, F32)
        for k in range(d.nk):                   # static unroll
            bid = ids_ref[0 if d.fixed else r, k]
            wb = _block_rows(w_ref, bid, bs, padded).astype(F32)  # (bs, G)
            dstage[_block_ds(bid, bs, padded), :] = _dot_nt(wb, dg) * d.scale
        return _unstage_blocks(dstage, H, bs, padded)
    m = m_ref[0].astype(F32)
    return (jnp.dot(dg, w_ref[...].astype(F32).T,
                    preferred_element_type=F32) * m * d.scale)


def _pl_wg(x, dg, acc_ref, ids_ref, m_ref, r, d, padded, stage):
    """Accumulate the site's weight grad into its f32 scratch in place."""
    if d.mode == "structured":
        bs = d.block_size
        _stage_blocks(x, stage, bs, padded)
        for k in range(d.nk):                   # static unroll
            bid = ids_ref[0 if d.fixed else r, k]
            rows = _block_ds(bid, bs, padded)
            acc_ref[rows, :] = acc_ref[rows, :] + jnp.dot(
                stage[rows, :], dg, preferred_element_type=F32) * d.scale
        return
    if d.mode == "dense":
        x = x * m_ref[0].astype(F32) * d.scale
    acc_ref[...] = acc_ref[...] + jnp.dot(x.T, dg,
                                          preferred_element_type=F32)


def _pl_bwd_kernel(*args, nl, descs, paddeds, n_steps, ragged):
    ns = 2 * nl
    i = 0
    ids_refs = args[i:i + ns]; i += ns                              # noqa: E702
    lens_ref = args[i]; i += 1                                      # noqa: E702
    dy = args[i]; i += 1                                            # noqa: E702
    gates = args[i:i + nl]; i += nl                                 # noqa: E702
    hh = args[i:i + nl]; i += nl                                    # noqa: E702
    hp = args[i:i + nl]; i += nl                                    # noqa: E702
    cc = args[i:i + nl]; i += nl                                    # noqa: E702
    cp = args[i:i + nl]; i += nl                                    # noqa: E702
    htil, fprev, alpha = args[i:i + 3]; i += 3                      # noqa: E702
    us = args[i:i + nl]; i += nl                                    # noqa: E702
    ws = args[i:i + nl - 1]; i += nl - 1                            # noqa: E702
    w_feed, w_comb, ep, eo = args[i:i + 4]; i += 4                  # noqa: E702
    dhT, dcT, dfT = args[i:i + 3]; i += 3                           # noqa: E702
    m_refs = args[i:i + ns]; i += ns                                # noqa: E702
    dgx0_r = args[i]; i += 1                                        # noqa: E702
    du_rs = args[i:i + nl]; i += nl                                 # noqa: E702
    dw_rs = args[i:i + nl - 1]; i += nl - 1                         # noqa: E702
    db_rs = args[i:i + nl - 1]; i += nl - 1                         # noqa: E702
    dwf_r, dwc_r, dep_r, deo_r = args[i:i + 4]; i += 4              # noqa: E702
    dh0_r, dc0_r, df0_r = args[i:i + 3]; i += 3                     # noqa: E702
    dh_s, dc_s, dfeed_s = args[i:i + 3]; i += 3                     # noqa: E702
    acc_s = args[i:i + ns]; i += ns                                 # noqa: E702
    db_s = args[i:i + nl - 1]; i += nl - 1                          # noqa: E702
    dwc_s, dep_s, deo_s = args[i:i + 3]; i += 3                     # noqa: E702
    stage, dstage = args[i:i + 2] if i < len(args) else (None, None)
    site_w = [w_feed] + list(us) + list(ws)
    H = dy.shape[-1]

    t = pl.program_id(0)
    r = n_steps - 1 - t                      # the time step being processed

    def bp(dg, s):
        return _pl_bp(dg, site_w[s], ids_refs[s], m_refs[s], r, descs[s], H,
                      paddeds[s], dstage)

    def wg(x, dg, s):
        _pl_wg(x, dg, acc_s[s], ids_refs[s], m_refs[s], r, descs[s],
               paddeds[s], stage)

    @pl.when(t == 0)
    def _init():
        dh_s[...] = dhT[...].astype(F32)
        dc_s[...] = dcT[...].astype(F32)
        dfeed_s[...] = dfT[...].astype(F32)
        for a in acc_s:
            a[...] = jnp.zeros_like(a)
        for a in db_s:
            a[...] = jnp.zeros_like(a)
        dwc_s[...] = jnp.zeros_like(dwc_s)
        dep_s[...] = jnp.zeros_like(dep_s)
        deo_s[...] = jnp.zeros_like(deo_s)

    htil_t = htil[0].astype(F32)
    alpha_t = alpha[0].astype(F32)
    eo32 = eo[...].astype(F32)
    ep32 = ep[...].astype(F32)
    cur = hh[nl - 1][0].astype(F32)

    dhtil = dy[0].astype(F32) + dfeed_s[...]
    if ragged:
        # frozen rows: zero the cotangents into the step math (linear in
        # them), pass the originals through to t-1 at the end.
        act = (r < lens_ref[...])[:, None]
        dhtil_c = jnp.where(act, dhtil, 0.0)
    else:
        act, dhtil_c = None, dhtil
    dpre = dhtil_c * (1.0 - htil_t * htil_t)
    ctxv = _pl_context(alpha_t, eo32)
    wc = w_comb[...].astype(F32)
    dwc_s[:H] = dwc_s[:H] + jnp.dot(ctxv.T, dpre,
                                    preferred_element_type=F32)
    dwc_s[H:] = dwc_s[H:] + jnp.dot(cur.T, dpre,
                                    preferred_element_type=F32)
    dctx = jnp.dot(dpre, wc[:H].T, preferred_element_type=F32)
    dcur = jnp.dot(dpre, wc[H:].T, preferred_element_type=F32)
    dalpha = _pl_scores(dctx, eo32)
    deo_s[...] = deo_s[...] + alpha_t[:, :, None] * dctx[:, None, :]
    dscores = alpha_t * (dalpha - jnp.sum(alpha_t * dalpha, -1,
                                          keepdims=True))
    dcur = dcur + _pl_context(dscores, ep32)
    dep_s[...] = dep_s[...] + dscores[:, :, None] * cur[:, None, :]

    dh_cur = [dh_s[l] for l in range(nl)]
    dh_cur[nl - 1] = dh_cur[nl - 1] + dcur
    new_dh, new_dc = [None] * nl, [None] * nl
    dfeed_prev = None
    for l in reversed(range(nl)):
        if ragged:
            dh_cell = jnp.where(act, dh_cur[l], 0.0)
            dc_cell = jnp.where(act, dc_s[l], 0.0)
        else:
            dh_cell, dc_cell = dh_cur[l], dc_s[l]
        dg, dc_prev = _pw_bwd(gates[l][0].astype(F32),
                              cp[l][0].astype(F32), cc[l][0].astype(F32),
                              dh_cell, dc_cell)
        new_dh[l] = bp(dg, 1 + l)
        wg(hp[l][0].astype(F32), dg, 1 + l)
        new_dc[l] = dc_prev
        if ragged:
            new_dh[l] = new_dh[l] + jnp.where(act, 0.0, dh_cur[l])
            new_dc[l] = new_dc[l] + jnp.where(act, 0.0, dc_s[l])
        if l > 0:
            dh_cur[l - 1] = dh_cur[l - 1] + bp(dg, nl + l)
            wg(hh[l - 1][0].astype(F32), dg, nl + l)
            db_s[l - 1][...] = db_s[l - 1][...] + dg.sum(axis=0)[None]
        else:
            dgx0_r[0] = dg.astype(dgx0_r.dtype)
            dfeed_prev = bp(dg, 0)
            if ragged:
                dfeed_prev = dfeed_prev + jnp.where(act, 0.0, dhtil)
            wg(fprev[0].astype(F32), dg, 0)
    for l in range(nl):
        dh_s[l] = new_dh[l]
        dc_s[l] = new_dc[l]
    dfeed_s[...] = dfeed_prev

    @pl.when(t == n_steps - 1)
    def _flush():
        dwf_r[...] = acc_s[0][...].astype(dwf_r.dtype)
        for l in range(nl):
            du_rs[l][...] = acc_s[1 + l][...].astype(du_rs[l].dtype)
        for l in range(1, nl):
            dw_rs[l - 1][...] = acc_s[nl + l][...].astype(dw_rs[l - 1].dtype)
            db_rs[l - 1][...] = db_s[l - 1][...].astype(db_rs[l - 1].dtype)
        dwc_r[...] = dwc_s[...].astype(dwc_r.dtype)
        dep_r[...] = dep_s[...].astype(dep_r.dtype)
        deo_r[...] = deo_s[...].astype(deo_r.dtype)
        dh0_r[...] = jnp.stack(new_dh).astype(dh0_r.dtype)
        dc0_r[...] = jnp.stack(new_dc).astype(dc0_r.dtype)
        df0_r[...] = dfeed_prev.astype(df0_r.dtype)


def _pallas_bwd(nl, descs, ops, masks, lengths, res, dout, *, interpret):
    gates_seqs, h_seqs, c_seqs, htil_seq, alpha_seq = res
    d_htil, d_hfin, d_cfin, d_ffin = dout
    T, B, G = ops["gx0"].shape
    H = ops["w_feed"].shape[0]
    S = ops["enc_out"].shape[1]
    ns = 2 * nl
    ragged = lengths is not None
    ids = [masks[i] if d.mode == "structured" else _dummy_ids()
           for i, d in enumerate(descs)]
    lens = lengths.astype(jnp.int32) if ragged else _dummy_lens()
    rev3 = lambda t, *_: (T - 1 - t, 0, 0)
    m_ins, m_specs = [], []
    for i, d in enumerate(descs):
        m_in, m_spec = _m3_inputs(masks[i] if d.mode == "dense" else None,
                                  F32, d.fixed, rev=rev3)
        m_ins.append(m_in)
        m_specs.append(m_spec)

    h_prev_seqs = tuple(
        jnp.concatenate([ops["h0"][l][None].astype(F32), h_seqs[l][:-1]])
        for l in range(nl))
    c_prev_seqs = tuple(
        jnp.concatenate([ops["c0"][l][None].astype(F32), c_seqs[l][:-1]])
        for l in range(nl))
    feed_prev_seq = jnp.concatenate(
        [ops["feed0"][None].astype(F32), htil_seq[:-1]])

    rev = lambda shp: pl.BlockSpec((1, *shp),
                                   lambda t, *_: (T - 1 - t,) + (0,) * len(shp))
    const = lambda shp: pl.BlockSpec(shp, lambda t, *_: (0,) * len(shp))
    w_feed, us, ws, paddeds, stage = _site_layout(nl, descs, ops, B)
    w_shapes = [w.shape for w in (w_feed, *us, *ws)]    # canonical site order

    kernel = functools.partial(_pl_bwd_kernel, nl=nl, descs=descs,
                               paddeds=paddeds, n_steps=T, ragged=ragged)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=ns + 1,
            grid=(T,),
            in_specs=[
                rev((B, H)),                                   # dy
                *([rev((B, G))] * nl),                         # gates_l
                *([rev((B, H))] * (4 * nl)),                   # h/h_prev/c/c_prev
                rev((B, H)), rev((B, H)), rev((B, S)),         # htil/fprev/alpha
                *[const(u.shape) for u in us],                 # U_l
                *[const(w.shape) for w in ws],                 # W_l
                const(w_feed.shape), const((2 * H, H)),        # w_feed/w_comb
                const((B, S, H)), const((B, S, H)),            # enc mem
                const((nl, B, H)), const((nl, B, H)),          # dhT/dcT
                const((B, H)),                                 # dfT
                *m_specs,
            ],
            out_specs=[
                rev((B, G)),                                   # dgx0
                *[const(shp) for shp in w_shapes[1:]],         # dU_l/dW_l
                *([const((1, G))] * (nl - 1)),                 # db_l
                const(w_shapes[0]), const((2 * H, H)),         # dWf/dWcomb
                const((B, S, H)), const((B, S, H)),            # dEp/dEo
                const((nl, B, H)), const((nl, B, H)),          # dh0/dc0
                const((B, H)),                                 # dfeed0
            ],
            scratch_shapes=[pltpu.VMEM((nl, B, H), F32),
                            pltpu.VMEM((nl, B, H), F32),
                            pltpu.VMEM((B, H), F32)]
            + [pltpu.VMEM(shp, F32) for shp in w_shapes]
            + [pltpu.VMEM((1, G), F32)] * (nl - 1)
            + [pltpu.VMEM((2 * H, H), F32),
               pltpu.VMEM((B, S, H), F32), pltpu.VMEM((B, S, H), F32)]
            + stage * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct((T, B, G), F32),
                   *[jax.ShapeDtypeStruct(shp, F32) for shp in w_shapes[1:]],
                   *[jax.ShapeDtypeStruct((1, G), F32)] * (nl - 1),
                   jax.ShapeDtypeStruct(w_shapes[0], F32),
                   jax.ShapeDtypeStruct((2 * H, H), F32),
                   jax.ShapeDtypeStruct((B, S, H), F32),
                   jax.ShapeDtypeStruct((B, S, H), F32),
                   jax.ShapeDtypeStruct((nl, B, H), F32),
                   jax.ShapeDtypeStruct((nl, B, H), F32),
                   jax.ShapeDtypeStruct((B, H), F32)],
        compiler_params=scan_compiler_params(),
        interpret=interpret,
    )(*ids, lens, d_htil, *gates_seqs, *h_seqs, *h_prev_seqs, *c_seqs,
      *c_prev_seqs, htil_seq, feed_prev_seq, alpha_seq, *us, *ws, w_feed,
      ops["w_comb"], ops["enc_proj"], ops["enc_out"], d_hfin, d_cfin, d_ffin,
      *m_ins)
    i = 0
    dgx = outs[i]; i += 1                                           # noqa: E702
    dus = list(outs[i:i + nl]); i += nl                             # noqa: E702
    dws = list(outs[i:i + nl - 1]); i += nl - 1                     # noqa: E702
    dbs = [b[0] for b in outs[i:i + nl - 1]]; i += nl - 1           # noqa: E702
    dwf, dwcomb, dep, deo, dh0, dc0, dfeed0 = outs[i:i + 7]
    accs = [_unpad_blocks(a, d.block_size, p) if d.mode == "structured"
            else a for a, d, p in zip([dwf] + dus + dws, descs, paddeds)]
    return (dgx, accs, dbs, dwcomb, dep, deo, dh0, dc0, dfeed0)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _decoder_scan(descs, impl, interpret, ops, masks, lengths):
    out, _ = _decoder_scan_fwd(descs, impl, interpret, ops, masks, lengths)
    return out


def _decoder_scan_fwd(descs, impl, interpret, ops, masks, lengths):
    nl = len(ops["us"])
    if impl == "pallas":
        (htil_seq, gates_seqs, h_seqs, c_seqs, alpha_seq,
         finals) = _pallas_fwd(nl, descs, ops, masks, lengths,
                               interpret=interpret)
    else:
        (htil_seq, gates_seqs, h_seqs, c_seqs, alpha_seq,
         finals) = _xla_fwd(nl, descs, ops, masks, lengths)
    h_fin, c_fin, feed_fin = finals
    odt = ops["gx0"].dtype
    out = (htil_seq.astype(odt), h_fin.astype(ops["h0"].dtype),
           c_fin.astype(ops["c0"].dtype), feed_fin.astype(odt))
    return out, (gates_seqs, h_seqs, c_seqs, htil_seq, alpha_seq, ops,
                 masks, lengths)


def _decoder_scan_bwd(descs, impl, interpret, res, dout):
    (gates_seqs, h_seqs, c_seqs, htil_seq, alpha_seq, ops, masks,
     lengths) = res
    nl = len(ops["us"])
    r = (gates_seqs, h_seqs, c_seqs, htil_seq, alpha_seq)
    if impl == "pallas":
        (dgx, accs, dbs, dwcomb, dep, deo, dh0, dc0, dfeed0) = _pallas_bwd(
            nl, descs, ops, masks, lengths, r, dout, interpret=interpret)
    else:
        (dgx, accs, dbs, dwcomb, dep, deo, dh0, dc0, dfeed0) = _xla_bwd(
            nl, descs, ops, masks, lengths, r, dout)
    d_ops = {
        "gx0": dgx.astype(ops["gx0"].dtype),
        "us": tuple(accs[1 + l].astype(ops["us"][l].dtype)
                    for l in range(nl)),
        "ws": tuple(accs[nl + l].astype(ops["ws"][l - 1].dtype)
                    for l in range(1, nl)),
        "bs": tuple(d.astype(b.dtype) for d, b in zip(dbs, ops["bs"])),
        "w_feed": accs[0].astype(ops["w_feed"].dtype),
        "w_comb": dwcomb.astype(ops["w_comb"].dtype),
        "enc_proj": dep.astype(ops["enc_proj"].dtype),
        "enc_out": deo.astype(ops["enc_out"].dtype),
        "score_bias": jnp.zeros_like(ops["score_bias"]),
        "h0": dh0.astype(ops["h0"].dtype),
        "c0": dc0.astype(ops["c0"].dtype),
        "feed0": dfeed0.astype(ops["feed0"].dtype),
    }
    d_masks = tuple(
        None if m is None else
        (_float0_like(m) if d.mode == "structured" else jnp.zeros_like(m))
        for d, m in zip(descs, masks))
    dlens = None if lengths is None else _float0_like(lengths)
    return d_ops, d_masks, dlens


_decoder_scan.defvjp(_decoder_scan_fwd, _decoder_scan_bwd)

_decoder_scan_jit = jax.jit(_decoder_scan, static_argnums=(0, 1, 2))


def decoder_scan(gx0: jax.Array, us: Tuple[jax.Array, ...],
                 ws: Tuple[jax.Array, ...], bs: Tuple[jax.Array, ...],
                 w_feed: jax.Array, w_comb: jax.Array,
                 enc_proj: jax.Array, enc_out: jax.Array,
                 score_bias: jax.Array, h0: jax.Array, c0: jax.Array,
                 feed0: jax.Array, *, sites,
                 impl: str = "xla", interpret: Optional[bool] = None,
                 lengths: Optional[jax.Array] = None):
    """Run the full teacher-forced decoder recurrence in one fused pass.

    gx0: (T, B, 4H) Phase-A gate inputs ``drop(embed_t) @ W_x + b_0``
    (time-batched outside, bias folded in); us: nl recurrent weights
    (H, 4H); ws/bs: the nl-1 upper-layer input weights (H, 4H) / biases
    (4H,); w_feed: (H, 4H) input-feed projection; w_comb: (2H, H);
    enc_proj = enc_out @ w_att and enc_out: (B, S, H) resident encoder
    memory; score_bias: (B, S) additive attention mask (0 kept / -1e30
    padded); h0/c0: (nl, B, H); feed0: (B, H). ``sites`` gives the 2*nl
    in-scan dropout sites in canonical order [feed, rh_0..rh_{nl-1},
    nr_1..nr_{nl-1}], each (keep_blocks|None, dense_mask|None, block_size,
    scale) — see the module docstring. Returns ``(h_tildes (T, B, H),
    (h_fin (nl, B, H), c_fin, feed_fin (B, H)))``, differentiable w.r.t.
    every array input (score_bias gets zero cotangent) through the fused
    hand-derived reverse-time backward.

    ``lengths`` (B,) int32 makes the target batch ragged: row b freezes
    every carry (h_l, c_l, feed) after its ``lengths[b]``-th step, so
    ``h_tildes[t, b]`` repeats the last valid readout for
    ``t >= lengths[b]``, finals are the states at the last real step, and
    frozen steps contribute exactly zero to every weight/attention
    gradient — equivalent to running each row unpacked at its own length.
    """
    nl = len(us)
    if len(sites) != 2 * nl:
        raise ValueError(f"need {2 * nl} site entries, got {len(sites)}")
    pairs = [_mk_site(*s) for s in sites]
    descs = tuple(p[0] for p in pairs)
    site_masks = tuple(p[1] for p in pairs)
    ops = dict(gx0=gx0, us=tuple(us), ws=tuple(ws), bs=tuple(bs),
               w_feed=w_feed, w_comb=w_comb, enc_proj=enc_proj,
               enc_out=enc_out, score_bias=score_bias, h0=h0, c0=c0,
               feed0=feed0)
    htil, h_fin, c_fin, feed_fin = _decoder_scan_jit(
        descs, impl, resolve_interpret(interpret), ops, site_masks, lengths)
    return htil, (h_fin, c_fin, feed_fin)
