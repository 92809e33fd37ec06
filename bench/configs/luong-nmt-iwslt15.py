"""Luong et al. (2015) attention NMT on IWSLT'15 En-Vi.

A two-layer LSTM encoder; a two-layer LSTM decoder started from the
encoder's final states, with input feeding (the previous step's attention
output enters layer 0 through its own matrix ``w_feed``), general
attention ``score = h_top . (enc W_att)`` over the real source positions,
``h~ = tanh(W_comb [context; h_top])``, and a dense output layer. Rows
are padded: the states run over every position, and the batch's masks
(``src_mask``, ``tgt_mask``) keep padding out of attention and out of
the loss, the mean NLL over the real target positions. The plain
reference below is written from those equations, in float32 with matmuls at the highest
precision; it imports nothing of the program. Dropout sites, as the
program names them: ``enc/layer<l>/nr|rh`` and ``dec/layer<l>/nr|rh``
per time step on each layer's input and recurrent input, ``dec/feed/nr``
on the fed-back attention output, ``enc/out`` on the encoder outputs and
``dec/out`` on the attention outputs before the output layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

KIND = "pairs"


def init_weights(key, sizes: dict, scale: float, dtype=jnp.float32):
    """The program's parameter tree, uniform in [-scale, scale]."""
    Vs, Vt, E, H, L = (sizes[k] for k in ("src_vocab", "tgt_vocab", "embed",
                                          "hidden", "num_layers"))
    ks = iter(jax.random.split(key, 7 + 6 * L))

    def u(shape):
        return jax.random.uniform(next(ks), shape, dtype, -scale, scale)

    def stack():
        return [{"W": u((E if l == 0 else H, 4 * H)), "U": u((H, 4 * H)),
                 "b": u((4 * H,))} for l in range(L)]

    return {"src_embed": u((Vs, E)), "tgt_embed": u((Vt, E)),
            "encoder": stack(), "decoder": stack(),
            "w_feed": u((H, 4 * H)), "w_att": {"w": u((H, H))},
            "w_comb": {"w": u((2 * H, H))},
            "fc": {"w": u((H, Vt)), "b": u((Vt,))}}


def _cell(g, c):
    i, f, gg, o = jnp.split(g, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(gg)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def _ones(m, shape, dtype):
    return jnp.ones(shape, dtype) if m is None else m


def ref_loss(params, batch, masks, precision):
    """Mean NLL over the real target positions under ``masks``."""
    dt = params["src_embed"].dtype

    def mm(a, b):
        return jnp.matmul(a, b, precision=precision)

    src, tin, tout = batch["src"], batch["tgt_in"], batch["tgt_out"]
    B, S = src.shape
    T = tin.shape[1]
    src_live, tgt_live = batch["src_mask"], batch["tgt_mask"] > 0
    E = params["src_embed"].shape[1]
    H = params["w_feed"].shape[0]
    L = len(params["encoder"])

    # encoder
    x = params["src_embed"][src].transpose(1, 0, 2)
    h_fin, c_fin = [], []
    for l, p in enumerate(params["encoder"]):
        D = x.shape[-1]
        mn = _ones(masks.per_step(f"enc/layer{l}/nr", S, B, D), (S, 1, D), dt)
        mr = _ones(masks.per_step(f"enc/layer{l}/rh", S, B, H), (S, 1, H), dt)

        def enc_step(carry, xs, p=p):
            h, c = carry
            xt, mnt, mrt = xs
            h, c = _cell(mm(xt * mnt, p["W"]) + mm(h * mrt, p["U"]) + p["b"],
                         c)
            return (h, c), h

        z = jnp.zeros((B, H), dt)
        (h, c), x = jax.lax.scan(enc_step, (z, z), (x, mn, mr))
        h_fin.append(h)
        c_fin.append(c)
    enc = x.transpose(1, 0, 2) * _ones(masks.whole("enc/out", (B, S), H),
                                       (H,), dt)
    enc_proj = mm(enc, params["w_att"]["w"])
    bias = jnp.where(src_live, 0.0, -1e30).astype(dt)

    # decoder, teacher-forced, with input feeding
    dec = params["decoder"]
    y = params["tgt_embed"][tin].transpose(1, 0, 2)
    m_in = _ones(masks.per_step("dec/layer0/nr", T, B, E), (T, 1, E), dt)
    m_feed = _ones(masks.per_step("dec/feed/nr", T, B, H), (T, 1, H), dt)
    m_rh = [_ones(masks.per_step(f"dec/layer{l}/rh", T, B, H), (T, 1, H), dt)
            for l in range(L)]
    m_nr = [_ones(masks.per_step(f"dec/layer{l}/nr", T, B, H), (T, 1, H), dt)
            for l in range(1, L)]

    def dec_step(carry, xs):
        hs, cs, feed = carry
        yt, mit, mft, mrt, mnt = xs
        new_h, new_c = [], []
        g = (mm(yt * mit, dec[0]["W"]) + dec[0]["b"]
             + mm(feed * mft, params["w_feed"]) + mm(hs[0] * mrt[0],
                                                      dec[0]["U"]))
        cur, c = _cell(g, cs[0])
        new_h.append(cur)
        new_c.append(c)
        for l in range(1, L):
            g = (mm(cur * mnt[l - 1], dec[l]["W"]) + dec[l]["b"]
                 + mm(hs[l] * mrt[l], dec[l]["U"]))
            cur, c = _cell(g, cs[l])
            new_h.append(cur)
            new_c.append(c)
        scores = jnp.einsum("bh,bsh->bs", cur, enc_proj,
                            precision=precision) + bias
        alpha = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bs,bsh->bh", alpha, enc, precision=precision)
        h_t = jnp.tanh(mm(jnp.concatenate([ctx, cur], -1),
                          params["w_comb"]["w"]))
        return (jnp.stack(new_h), jnp.stack(new_c), h_t), h_t

    carry = (jnp.stack(h_fin), jnp.stack(c_fin), jnp.zeros((B, H), dt))
    _, ht = jax.lax.scan(dec_step, carry,
                         (y, m_in, m_feed, tuple(m_rh), tuple(m_nr)))
    ht = ht.transpose(1, 0, 2) * _ones(masks.whole("dec/out", (B, T), H),
                                       (H,), dt)
    logits = mm(ht, params["fc"]["w"]) + params["fc"]["b"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               tout[..., None], -1)[..., 0]
    live = tgt_live.astype(nll.dtype)
    return ((nll * live).sum() / jnp.maximum(live.sum(), 1)
            ).astype(jnp.float32)


def _lengths(batch):
    return batch["src_mask"].sum(1), (batch["tgt_mask"] > 0).sum(1)


def loss_tokens(batch) -> int:
    return int(_lengths(batch)[1].sum())


# -- required work ---------------------------------------------------------
# ``kept(site, dim)`` is the number of input rows a matmul needs when the
# site's dropout is applied directly to its input: the kept units of a
# structured site, all ``dim`` otherwise (see README.md). Only real
# positions count: a row's source and target tokens up to its lengths,
# and attention over its real source positions.


def _real(batch):
    sl, tl = (x.astype("float64") for x in _lengths(batch))
    return float(sl.sum()), float(tl.sum()), float((sl * tl).sum())


def _decoder_flops(sizes, kept, tgt, pairs):
    H, L = sizes["hidden"], sizes["num_layers"]
    per_tok = 2 * kept("dec/feed/nr", H) * 4 * H
    for l in range(L):
        per_tok += 2 * kept(f"dec/layer{l}/rh", H) * 4 * H
        if l:
            per_tok += 2 * kept(f"dec/layer{l}/nr", H) * 4 * H
    per_tok += 2 * 2 * H * H                       # W_comb [context; h_top]
    return per_tok * tgt + 2 * 2 * H * pairs        # scores + context


def step_flops(sizes: dict, kept, batch) -> float:
    """Matmul FLOPs one training step needs: forward x 3."""
    E, H, L, Vt = (sizes[k] for k in ("embed", "hidden", "num_layers",
                                      "tgt_vocab"))
    src, tgt, pairs = _real(batch)
    enc = 0
    for l in range(L):
        enc += 2 * kept(f"enc/layer{l}/nr", E if l == 0 else H) * 4 * H
        enc += 2 * kept(f"enc/layer{l}/rh", H) * 4 * H
    enc += 2 * kept("enc/out", H) * H              # W_att
    dec = 2 * kept("dec/layer0/nr", E) * 4 * H + 2 * kept("dec/out", H) * Vt
    fwd = (enc * src + dec * tgt
           + _decoder_flops(sizes, kept, tgt, pairs))
    return 3.0 * fwd


def kernel_work(sizes: dict, kept, batch) -> dict:
    """{kernel: (flops, bytes)} one training step needs of each recurrent
    scan kernel, forward and backward, summed over its calls. Bytes are
    the float32 arrays at real positions that a call has to read and
    write in HBM: its inputs and outputs, then in the backward the inputs
    again, the output cotangents and the input gradients; no saved
    residuals. Of a weight behind a per-step site only the rows that some
    step keeps count (``kept(site, dim, steps)``), over the longest
    row's steps."""
    H, L = sizes["hidden"], sizes["num_layers"]
    B = batch["src"].shape[0]
    src, tgt, pairs = _real(batch)
    S, T = (int(x.max()) for x in _lengths(batch))
    # encoder layers: lstm_scan
    enc_flops = enc_bytes = 0.0
    for l in range(L):
        rh = f"enc/layer{l}/rh"
        enc_flops += 3 * 2 * src * kept(rh, H) * 4 * H
        gx, ys, st = src * 4 * H, src * H, 2 * B * H
        w = kept(rh, H, S) * 4 * H
        enc_bytes += 4.0 * ((gx + w + st) + (ys + st)
                            + (gx + w + st) + (ys + st) + (gx + w + st))
    # the decoder's recurrence and attention: decoder_scan
    weights = (kept("dec/feed/nr", H, T) * 4 * H + 2 * H * H
               + sum(kept(f"dec/layer{l}/rh", H, T) * 4 * H
                     for l in range(L))
               + sum(kept(f"dec/layer{l}/nr", H, T) * 4 * H + 4 * H
                     for l in range(1, L)))
    ins = tgt * 4 * H + 2 * src * H + src + (2 * L + 1) * B * H + weights
    outs = tgt * H
    dec_bytes = 4.0 * (ins + outs + ins + outs + ins)
    dec_flops = 3.0 * _decoder_flops(sizes, kept, tgt, pairs)
    return {"lstm_scan": (enc_flops, enc_bytes),
            "decoder_scan": (dec_flops, dec_bytes)}
