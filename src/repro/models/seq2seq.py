"""Luong'15 attention NMT (paper Table 2): 2-layer unidirectional LSTM
encoder-decoder with general attention + input feeding.

Dropout comes from a ``DropoutPlan`` over named sites — "nr" / "rh" resolve
for both stacks (full site names "enc/layer0/nr", "dec/feed/nr", ... keep
the PRNG streams independent), and "out" covers the encoder/decoder output
dropout of the paper's §4.2 modification.

``cfg.engine`` selects the recurrent execution path for BOTH stacks. The
encoder runs the standard lstm_stack engines. The decoder historically kept
its whole NR matmul in-scan — input feeding makes step t's NR input
``[embed_t ; h~_{t-1}]`` depend on step t-1's attention output — but that
joint matmul splits exactly:

    [embed_t ; h~_{t-1}] @ W  ==  embed_t @ W  +  h~_{t-1} @ W_feed

so the decoder params keep W with embed-only fan-in plus a separate
``w_feed``, and teacher-forced decoding is TWO PASSES:

  * **pass 1** — the recurrence. The embed half of layer 0's NR matmul has
    no sequential dependence: it hoists out of the scan and runs
    time-batched through ``dense_sdrop_scheduled`` at (1-p) FLOPs (site
    "dec/layer0/nr", bias folded in). The feed half stays recurrent and is
    carried INSIDE the scan as one more compact-gathered matmul (site
    "dec/feed/nr") next to the RH matmuls; attention cannot leave the scan
    (h~_{t-1} -> gates_t -> h_t -> attention_t -> h~_t is a nonlinear
    chain) so each step's Luong attention + h~ readout runs in-scan too.
    Under ``engine="fused"`` the whole pass is ONE ``kernels.decoder_scan``
    call with a hand-derived fused reverse-time backward, so fwd AND bwd
    run at (1-p) recurrent FLOPs; ``engine="scheduled"`` is the same
    restructure as a lax.scan; ``engine="stepwise"`` is the per-step-mask
    in-scan oracle.
  * **pass 2** — everything after the h~ sequence exists is time-batched:
    output dropout ("dec/out") + the vocab projection over all T steps at
    once. (Attention already ran in pass 1 — its per-step outputs are the
    recurrent feed — so pass 2 has no per-step work left.)

This restructure is exact only under teacher forcing (the target inputs
for all T steps are known up front). Free-running inference uses the
single-step path: ``init_state`` / ``prefill`` / ``decode_step`` below
serve through ``serving.DecodeEngine`` token by token.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import layers as L
from repro.core import lstm as lstm_mod
from repro.core import metrics
from repro.core.dropout_plan import DropoutPlan
from repro.core.sdrop import DropoutSpec
from repro.launch import trace_names as tn


@dataclasses.dataclass(frozen=True)
class NMTConfig:
    name: str = "luong_nmt"
    src_vocab: int = 50000
    tgt_vocab: int = 50000
    embed: int = 512
    hidden: int = 512
    num_layers: int = 2
    plan: DropoutPlan = DropoutPlan({"nr": DropoutSpec(rate=0.3)})
    engine: str = "scheduled"      # recurrent engine (see module docstring)
    param_dtype: Any = jnp.float32


def init_params(key, cfg: NMTConfig):
    ks = jax.random.split(key, 8)
    H = cfg.hidden
    return {
        "src_embed": L.uniform_init(ks[0], (cfg.src_vocab, cfg.embed), 0.1),
        "tgt_embed": L.uniform_init(ks[1], (cfg.tgt_vocab, cfg.embed), 0.1),
        "encoder": lstm_mod.init_lstm_params(ks[2], cfg.embed, H,
                                             cfg.num_layers),
        # decoder layer 0 consumes the embed only; the input-feed half of
        # the old joint [embed ; h~] matmul is the separate w_feed below
        "decoder": lstm_mod.init_lstm_params(ks[3], cfg.embed, H,
                                             cfg.num_layers),
        "w_feed": L.uniform_init(ks[7], (H, 4 * H), 0.05),
        "w_att": L.init_dense(ks[4], H, H, bias=False),     # general score
        "w_comb": L.init_dense(ks[5], 2 * H, H, bias=False),
        "fc": L.init_dense(ks[6], H, cfg.tgt_vocab),
    }


def encode(params, src, cfg: NMTConfig, *, ctx=None, lengths=None):
    """src (B, S) -> (enc_out (B, S, H), final state).

    ``lengths`` (B,) int32 marks ragged sources: each row's encoder state
    freezes at its last real token, so the state handed to the decoder is
    the same one an unpacked per-row encode would produce."""
    if ctx is None:
        ctx = cfg.plan.bind(None)
    B, S = src.shape
    with jax.named_scope(tn.EMBED):
        x = jnp.take(params["src_embed"], src, axis=0).transpose(1, 0, 2)
    state = lstm_mod.zero_state(cfg.num_layers, B, cfg.hidden)
    ys, state = lstm_mod.lstm_stack(
        params["encoder"], x, state, ctx=ctx, site="enc",
        engine=cfg.engine, lengths=lengths)
    with jax.named_scope(tn.OUTPUT):
        enc = ctx.apply("enc/out", ys.transpose(1, 0, 2))  # (B,S,H)
    return enc, state


def _scan_site_names(nl):
    """The decoder's IN-SCAN dropout sites, in ``kernels.decoder_scan``'s
    canonical order [feed, rh_0..rh_{nl-1}, nr_1..nr_{nl-1}]. (Layer 0's
    NR site "dec/layer0/nr" is the hoisted Phase-A one — not in-scan.)"""
    return (["dec/feed/nr"]
            + [f"dec/layer{l}/rh" for l in range(nl)]
            + [f"dec/layer{l}/nr" for l in range(1, nl)])


def _attend(params, cur, enc_proj, enc_out, score_bias):
    """Luong general attention + h~ readout for one step's top state."""
    with jax.named_scope(tn.ATTENTION):
        scores = jnp.einsum("bh,bsh->bs", cur, enc_proj) + score_bias
        alpha = jax.nn.softmax(scores, axis=-1)
        ctx_vec = jnp.einsum("bs,bsh->bh", alpha, enc_out)
        return jnp.tanh(L.dense(params["w_comb"],
                                jnp.concatenate([ctx_vec, cur], -1)))


def _dec_step(params, nl, carry, gx0_t, sts, enc_proj, enc_out, score_bias):
    """One decoder step given the precomputed layer-0 embed gates ``gx0_t``
    (bias folded) and the in-scan sites' DropoutStates ``sts`` (canonical
    order, None = eval)."""
    dec = params["decoder"]
    hs, cs, feed = carry
    g = (gx0_t
         + L.dense_sdrop({"w": params["w_feed"]}, feed, sts[0])
         + L.dense_sdrop({"w": dec[0]["U"]}, hs[0], sts[1]))
    h, c = lstm_mod.lstm_pointwise(g, cs[0])
    new_h, new_c = [h], [c]
    cur = h
    for l in range(1, nl):
        g = (L.dense_sdrop({"w": dec[l]["W"], "b": dec[l]["b"]}, cur,
                           sts[nl + l])
             + L.dense_sdrop({"w": dec[l]["U"]}, hs[l], sts[1 + l]))
        h, c = lstm_mod.lstm_pointwise(g, cs[l])
        new_h.append(h)
        new_c.append(c)
        cur = h
    h_tilde = _attend(params, cur, enc_proj, enc_out, score_bias)
    return (jnp.stack(new_h), jnp.stack(new_c), h_tilde), h_tilde


def _site_args(sched):
    """MaskSchedule -> decoder_scan's (keep_blocks, dense_mask, bs, scale)."""
    if sched.inactive:
        return (None, None, 1, 1.0)
    if sched.structured:
        return (sched.keep_blocks, None, sched.spec.block_size, sched.scale)
    return (None, sched.dense_mask, 1, sched.scale)


def decode_train(params, tgt_in, enc_out, enc_state, cfg: NMTConfig, *,
                 ctx=None, src_mask=None, tgt_lengths=None):
    """Teacher-forced decoding with Luong general attention + input feeding.

    tgt_in: (B, St); enc_out: (B, Ss, H). Returns logits (B, St, V).
    Two-pass restructure per the module docstring; ``cfg.engine`` picks the
    pass-1 execution (stepwise oracle / scheduled scan / fused kernel).
    ``tgt_lengths`` (B,) int32 marks ragged targets: every decoder carry
    (h_l, c_l, feed) freezes past each row's length and frozen steps cost
    zero gradient — identical across all three engines.
    """
    if ctx is None:
        ctx = cfg.plan.bind(None)
    B, St = tgt_in.shape
    H = cfg.hidden
    nl = cfg.num_layers
    dec = params["decoder"]
    with jax.named_scope(tn.EMBED):
        x = jnp.take(params["tgt_embed"], tgt_in, axis=0)  # (B,St,E)
        x_seq = x.transpose(1, 0, 2)                       # (St,B,E)
    with jax.named_scope(tn.ATTENTION):
        enc_proj = L.dense(params["w_att"], enc_out)       # (B,Ss,H)
        if src_mask is None:
            src_mask = jnp.ones(enc_out.shape[:2], bool)
        score_bias = jnp.where(src_mask, 0.0, -1e30).astype(jnp.float32)
    h0, c0 = enc_state.h, enc_state.c
    feed0 = jnp.zeros((B, H), x.dtype)
    site_names = _scan_site_names(nl)

    def freeze(carry_new, carry_old, t):
        """Ragged carry freeze: rows past their length keep t-1's state."""
        if tgt_lengths is None:
            return carry_new
        act = t < tgt_lengths                              # (B,)
        nh, nc, nf = carry_new
        oh, oc, of_ = carry_old
        return (jnp.where(act[None, :, None], nh, oh),
                jnp.where(act[None, :, None], nc, oc),
                jnp.where(act[:, None], nf, of_))

    if cfg.engine == "stepwise":
        # oracle: everything in-scan, masks drawn per step via ctx.state
        # (row t of a schedule is bit-identical — same per-step key).
        def step(carry, xs):
            x_t, t = xs
            gx0_t = L.dense_sdrop(
                {"w": dec[0]["W"], "b": dec[0]["b"]}, x_t,
                ctx.state("dec/layer0/nr", B, cfg.embed, t=t))
            sts = [ctx.state(n, B, H, t=t) for n in site_names]
            new_carry, _ = _dec_step(params, nl, carry, gx0_t, sts,
                                     enc_proj, enc_out, score_bias)
            new_carry = freeze(new_carry, carry, t)
            return new_carry, new_carry[2]

        _, h_tildes = jax.lax.scan(step, (h0, c0, feed0),
                                   (x_seq, jnp.arange(St)))
    else:
        # Phase A (both remaining engines): the hoisted embed-half NR
        # matmul, time-batched + compacted at (1-p) FLOPs, bias folded.
        with tn.scope(tn.PHASE_A, ctx.spec("dec/layer0/nr").impl):
            gx0 = L.dense_sdrop_scheduled(
                {"w": dec[0]["W"], "b": dec[0]["b"]}, x_seq,
                ctx.schedule("dec/layer0/nr", St, B, cfg.embed))
            scheds = [ctx.schedule(n, St, B, H) for n in site_names]
        if cfg.engine == "fused":
            from repro.kernels import ops as _kops
            nr0 = ctx.spec("dec/layer0/nr")
            impl = next((s.spec.impl for s in scheds if not s.inactive),
                        nr0.impl if nr0.active else "xla")
            h_tildes, _ = _kops.decoder_scan(
                gx0, tuple(p["U"] for p in dec),
                tuple(p["W"] for p in dec[1:]),
                tuple(p["b"] for p in dec[1:]),
                params["w_feed"], params["w_comb"]["w"], enc_proj, enc_out,
                score_bias, h0, c0, feed0,
                sites=tuple(_site_args(s) for s in scheds), impl=impl,
                lengths=tgt_lengths)
        else:
            # scheduled: same restructure as a slim lax.scan. PER_STEP
            # mask rows ride through as xs, FIXED ones close over as
            # constants — no PRNG and no embed matmul in the body.
            xs_rows = tuple(s.scan_rows() for s in scheds)
            consts = [s.state(0) if r is None else None
                      for s, r in zip(scheds, xs_rows)]

            def step(carry, xs):
                gx0_t, rows, t = xs
                sts = [consts[i] if rows[i] is None
                       else scheds[i].state_for_row(rows[i])
                       for i in range(len(scheds))]
                new_carry, _ = _dec_step(params, nl, carry, gx0_t, sts,
                                         enc_proj, enc_out, score_bias)
                new_carry = freeze(new_carry, carry, t)
                return new_carry, new_carry[2]

            _, h_tildes = jax.lax.scan(step, (h0, c0, feed0),
                                       (gx0, xs_rows, jnp.arange(St)))
    # pass 2: time-batched output dropout + vocab projection.
    with jax.named_scope(tn.OUTPUT):
        ht = h_tildes.transpose(1, 0, 2)                   # (B,St,H)
        ht = ctx.apply("dec/out", ht)
        return L.dense(params["fc"], ht).astype(jnp.float32)


def loss_fn(params, batch, cfg: NMTConfig, *, drop_key=None, rules=None,
            step=0, shard=None):
    """batch: {"src", "tgt_in", "tgt_out", ["src_mask", "tgt_mask",
    "src_lengths", "tgt_lengths"]}.

    Token-packed batches carry "src_lengths"/"tgt_lengths" (B,) int32
    instead of (or in addition to) the boolean masks: lengths freeze the
    recurrent carries inside both stacks (real FLOPs/grad savings, see
    kernels/cell_scan.py) and also derive the attention/loss masks when
    those aren't supplied explicitly.
    """
    ctx = cfg.plan.bind(drop_key, step, shard=shard)
    src_lengths = batch.get("src_lengths")
    tgt_lengths = batch.get("tgt_lengths")
    enc, st = encode(params, batch["src"], cfg, ctx=ctx,
                     lengths=src_lengths)
    src_mask = batch.get("src_mask")
    if src_mask is None and src_lengths is not None:
        src_mask = metrics.length_mask(src_lengths,
                                       batch["src"].shape[1]) > 0
    logits = decode_train(params, batch["tgt_in"], enc, st, cfg,
                          ctx=ctx, src_mask=src_mask,
                          tgt_lengths=tgt_lengths)
    with jax.named_scope(tn.LOSS):
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(lp, batch["tgt_out"][..., None],
                                   -1)[..., 0]
        mask = batch.get("tgt_mask")
        if mask is None and tgt_lengths is not None:
            mask = metrics.length_mask(tgt_lengths,
                                       batch["tgt_in"].shape[1])
        if mask is not None:
            return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
        return nll.mean()


# ---------------------------------------------------------------------------
# serving: free-running inference stays on the single-step path (the
# two-pass restructure needs all T inputs up front — teacher forcing).
# ---------------------------------------------------------------------------


def init_state(cfg: NMTConfig, batch: int, max_src: int):
    """Fresh decode state (every leaf batch-at-axis-1 for slot scatter).

    ``score_bias`` starts all -1e30: before prefill the softmax is uniform
    over zero encoder memory (finite, contributes nothing)."""
    nl, H = cfg.num_layers, cfg.hidden
    f32 = jnp.float32
    return {
        "h": jnp.zeros((nl, batch, H), f32),
        "c": jnp.zeros((nl, batch, H), f32),
        "feed": jnp.zeros((1, batch, H), f32),
        "enc_out": jnp.zeros((1, batch, max_src, H), f32),
        "enc_proj": jnp.zeros((1, batch, max_src, H), f32),
        "score_bias": jnp.full((1, batch, max_src), -1e30, f32),
    }


def _eval_step(params, nl, x_t, h, c, feed, enc_proj, enc_out, score_bias):
    """One no-dropout decoder step (the training step with eval states)."""
    dec = params["decoder"]
    gx0_t = L.dense_sdrop({"w": dec[0]["W"], "b": dec[0]["b"]}, x_t, None)
    (h, c, h_tilde), _ = _dec_step(params, nl, (h, c, feed), gx0_t,
                                   [None] * (2 * nl), enc_proj, enc_out,
                                   score_bias)
    return h, c, h_tilde


def prefill(params, batch, cfg: NMTConfig, state, *, rules=None):
    """Fill the decode state from {"src", "tgt_in", ["src_mask"]}: run the
    encoder, park its memory (enc_out / enc_proj / score_bias) in the
    state, then replay the target prefix through eval decoder steps so
    (h, c, feed) sit exactly where teacher-forced decoding left them."""
    del rules
    src = batch["src"]
    B, Ss = src.shape
    enc, enc_state = encode(params, src, cfg)              # eval ctx
    enc_proj = L.dense(params["w_att"], enc)
    src_mask = batch.get("src_mask")
    if src_mask is None:
        src_mask = jnp.ones((B, Ss), bool)
    sb = jnp.where(src_mask, 0.0, -1e30).astype(jnp.float32)
    state = dict(state)
    state["enc_out"] = state["enc_out"].at[0, :, :Ss, :].set(enc)
    state["enc_proj"] = state["enc_proj"].at[0, :, :Ss, :].set(enc_proj)
    state["score_bias"] = (jnp.full_like(state["score_bias"], -1e30)
                           .at[0, :, :Ss].set(sb))
    nl, H = cfg.num_layers, cfg.hidden
    ep, eo, sbf = state["enc_proj"][0], state["enc_out"][0], \
        state["score_bias"][0]
    x = jnp.take(params["tgt_embed"], batch["tgt_in"], axis=0)

    def step(carry, x_t):
        h, c, feed = carry
        h, c, h_tilde = _eval_step(params, nl, x_t, h, c, feed, ep, eo, sbf)
        return (h, c, h_tilde), None

    feed0 = jnp.zeros((B, H), enc.dtype)
    (h, c, feed), _ = jax.lax.scan(
        step, (enc_state.h, enc_state.c, feed0), x.transpose(1, 0, 2))
    state["h"], state["c"], state["feed"] = h, c, feed[None]
    return None, state


def decode_step(params, cfg: NMTConfig, state, tokens, pos, *, rules=None):
    """One serving decode step: tokens (B, 1) -> (logits (B, 1, V), state).
    ``pos`` is ignored — the recurrent state is O(1) in position."""
    del pos, rules
    x_t = jnp.take(params["tgt_embed"], tokens[:, 0], axis=0)
    h, c, h_tilde = _eval_step(
        params, cfg.num_layers, x_t, state["h"], state["c"],
        state["feed"][0], state["enc_proj"][0], state["enc_out"][0],
        state["score_bias"][0])
    logits = L.dense(params["fc"], h_tilde).astype(jnp.float32)[:, None]
    state = {**state, "h": h, "c": c, "feed": h_tilde[None]}
    return logits, state
