"""Fused LSTM gate pointwise Pallas kernel.

After the (compacted) gate matmuls produce ``gates = xW + hU + b`` (B, 4H),
the cell update is 8 elementwise HBM round-trips if left to XLA on a memory-
bound part of the step. This kernel keeps one (bm, bh) tile of all four gates
plus c_prev resident in VMEM and emits h', c' in a single pass:

    c' = sigmoid(f + fb) * c + sigmoid(i) * tanh(g)
    h' = sigmoid(o) * tanh(c')

Gate layout matches core.lstm: gates[:, 0:H]=i, [H:2H]=f, [2H:3H]=g, [3H:4H]=o.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _kernel(i_ref, f_ref, g_ref, o_ref, c_ref, h_out, c_out, *, forget_bias):
    i = i_ref[...].astype(jnp.float32)
    f = f_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    o = o_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    c_new = jax.nn.sigmoid(f + forget_bias) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    h_out[...] = h_new.astype(h_out.dtype)
    c_out[...] = c_new.astype(c_out.dtype)


@functools.partial(jax.jit, static_argnames=("forget_bias", "bm", "bh", "interpret"))
def lstm_pointwise(gates: jax.Array, c_prev: jax.Array, *,
                   forget_bias: float = 0.0,
                   bm: Optional[int] = None,
                   bh: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """gates: (B, 4H), c_prev: (B, H) -> (h', c') each (B, H)."""
    interpret = resolve_interpret(interpret)
    B, H4 = gates.shape
    H = H4 // 4
    assert c_prev.shape == (B, H)
    bm = bm or min(128, B)
    bh = bh or min(512, H)
    # Require exact tiling; callers pad (LSTM hidden sizes are config-chosen).
    if B % bm or H % bh:
        pad_b, pad_h = (-B) % bm, (-H) % bh
        gates = jnp.pad(gates.reshape(B, 4, H), ((0, pad_b), (0, 0), (0, pad_h))
                        ).reshape(B + pad_b, 4 * (H + pad_h))
        c_prev = jnp.pad(c_prev, ((0, pad_b), (0, pad_h)))
        h, c = lstm_pointwise(gates, c_prev, forget_bias=forget_bias,
                              bm=bm, bh=bh, interpret=interpret)
        return h[:B, :H], c[:B, :H]

    grid = (B // bm, H // bh)
    Hp = H

    def gate_spec(idx):
        return pl.BlockSpec((bm, bh), lambda i, j: (i, idx * (Hp // bh) + j))

    specs = [gate_spec(0), gate_spec(1), gate_spec(2), gate_spec(3),
             pl.BlockSpec((bm, bh), lambda i, j: (i, j))]
    out_spec = pl.BlockSpec((bm, bh), lambda i, j: (i, j))
    h, c = pl.pallas_call(
        functools.partial(_kernel, forget_bias=forget_bias),
        grid=grid,
        in_specs=specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H), gates.dtype),
                   jax.ShapeDtypeStruct((B, H), gates.dtype)],
        interpret=interpret,
    )(gates, gates, gates, gates, c_prev)
    return h, c
