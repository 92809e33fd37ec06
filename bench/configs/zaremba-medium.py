"""Zaremba et al. (2014), medium: the PTB word-level LSTM language model.

Embedding, two LSTM layers (gate order i, f, g, o; no forget bias), a
dense output layer and the mean next-token cross-entropy. The plain
reference below is written from the paper's equations, in float32 with
matmuls at the highest precision; it imports nothing of the program.
Dropout sites, as the program names them: ``embed`` on the embedding
output, ``lstm/layer<l>/nr`` on each layer's input and
``lstm/layer<l>/rh`` on its recurrent input (per time step), ``out`` on
the last layer's output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

KIND = "lm"


def init_weights(key, sizes: dict, scale: float, dtype=jnp.float32):
    """The program's parameter tree, uniform in [-scale, scale]."""
    V, E, H, L = (sizes[k] for k in ("vocab", "embed", "hidden",
                                     "num_layers"))
    ks = iter(jax.random.split(key, 3 + 3 * L))

    def u(shape):
        return jax.random.uniform(next(ks), shape, dtype, -scale, scale)

    layers = []
    for l in range(L):
        d = E if l == 0 else H
        layers.append({"W": u((d, 4 * H)), "U": u((H, 4 * H)),
                       "b": u((4 * H,))})
    return {"embed": u((V, E)), "lstm": layers,
            "fc": {"w": u((H, V)), "b": u((V,))}}


def _cell(g, c):
    i, f, gg, o = jnp.split(g, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(gg)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def _ones(m, shape, dtype):
    return jnp.ones(shape, dtype) if m is None else m


def ref_loss(params, batch, masks, precision):
    """Mean next-token NLL of one batch under ``masks`` (dropout_ref)."""
    dt = params["embed"].dtype

    def mm(a, b):
        return jnp.matmul(a, b, precision=precision)

    tok, lab = batch["tokens"], batch["labels"]
    B, T = tok.shape
    E = params["embed"].shape[1]
    x = params["embed"][tok] * _ones(masks.whole("embed", (B, T), E),
                                     (E,), dt)
    x = x.transpose(1, 0, 2)
    for l, p in enumerate(params["lstm"]):
        D, H = x.shape[-1], p["U"].shape[0]
        mn = _ones(masks.per_step(f"lstm/layer{l}/nr", T, B, D),
                   (T, 1, D), dt)
        mr = _ones(masks.per_step(f"lstm/layer{l}/rh", T, B, H),
                   (T, 1, H), dt)

        def step(carry, xs, p=p):
            h, c = carry
            xt, mnt, mrt = xs
            h, c = _cell(mm(xt * mnt, p["W"]) + mm(h * mrt, p["U"]) + p["b"],
                         c)
            return (h, c), h

        z = jnp.zeros((B, H), dt)
        _, x = jax.lax.scan(step, (z, z), (x, mn, mr))
    H = x.shape[-1]
    h = x.transpose(1, 0, 2) * _ones(masks.whole("out", (B, T), H), (H,), dt)
    logits = mm(h, params["fc"]["w"]) + params["fc"]["b"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               lab[..., None], -1)
    return nll.mean().astype(jnp.float32)


def loss_tokens(batch) -> int:
    return int(batch["labels"].size)


# -- required work ---------------------------------------------------------
# ``kept(site, dim)`` is the number of input rows a matmul needs when the
# site's dropout is applied directly to its input: the kept units of a
# structured site, all ``dim`` otherwise (see README.md).


def step_flops(sizes: dict, kept, batch) -> float:
    """Matmul FLOPs one training step needs: forward x 3."""
    V, E, H, L = (sizes[k] for k in ("vocab", "embed", "hidden",
                                     "num_layers"))
    tokens = batch["tokens"].size
    per_token = 0
    for l in range(L):
        d = E if l == 0 else H
        per_token += 2 * kept(f"lstm/layer{l}/nr", d) * 4 * H
        per_token += 2 * kept(f"lstm/layer{l}/rh", H) * 4 * H
    per_token += 2 * kept("out", H) * V
    return 3.0 * per_token * tokens


def kernel_work(sizes: dict, kept, batch) -> dict:
    """{kernel: (flops, bytes)} one training step needs of each recurrent
    scan kernel, forward and backward, summed over its calls. Bytes are
    the float32 arrays a call has to read and write in HBM: its inputs
    and outputs, then in the backward the inputs again, the output
    cotangents and the input gradients; no saved residuals. Of the
    recurrent weight U and its gradient only the rows that some step of
    the scan keeps count (``kept(site, H, T)``); with per-step masks over
    35 steps that is all of them but for a 2**-35 share."""
    H, L = sizes["hidden"], sizes["num_layers"]
    B, T = batch["tokens"].shape
    flops = nbytes = 0.0
    for l in range(L):
        rh = f"lstm/layer{l}/rh"
        flops += 3 * 2 * B * T * kept(rh, H) * 4 * H
        gx, ys, st = B * T * 4 * H, B * T * H, 2 * B * H
        w = kept(rh, H, T) * 4 * H
        fwd = (gx + w + st) + (ys + st)
        bwd = (gx + w + st) + (ys + st) + (gx + w + st)
        nbytes += 4.0 * (fwd + bwd)
    return {"lstm_scan": (flops, nbytes)}
