"""Fused persistent-scan LSTM — the vanilla-cell instance of cell_scan.

The whole T-step Phase-B LSTM recurrence runs in one ``pallas_call``
(``kernels/cell_scan.py`` holds the shared machinery):

  * U is loaded into VMEM **once** and stays resident across all T steps
    (constant BlockSpec index_map; the time axis is the grid, and TPU grid
    steps on one core run sequentially, so the pipeline never evicts it);
  * the carried (h, c) state lives in VMEM scratch, never round-tripping
    to HBM between steps;
  * the paper's RH structured dropout gathers each step's kept hidden-unit
    blocks straight out of the resident U via the scalar-prefetched
    ``(T, nk)`` MaskSchedule ids table — the recurrent matmul runs at
    (1-p) FLOPs with zero-cost gathers (``nk`` static, exact-k masks);
  * the LSTM pointwise update (this module: sigmoid/tanh gate math on
    pre-activation gates in order i,f,g,o) is fused into the same pass;
  * a ``custom_vjp`` reverse-time kernel makes the backward equally fused:
    dgates elementwise from the stored pre-activation gates + c sequence,
    BP/WG gathered compact, dU accumulated in f32 VMEM scratch and flushed
    once. Forward *and* backward recurrent matmuls run at (1-p) FLOPs.

Three RH modes (selected by which mask argument is given): ``keep_blocks``
(T|1, nk) structured ids table (compact gathers); ``dense_mask``
(T|1, B, H) random mask (mask-multiply then dense matmul — regularization
only, no reclaim); neither = dense recurrence. A leading 1 row is a FIXED
time pattern (one mask reused every step).

``impl="xla"`` is the production CPU path: the same fused two-pass
structure expressed as ``lax.scan``s with compact structured gathers. Its
edge over "scheduled" is the hand-written backward: dU accumulates as a
compact in-place scatter-add on the scan carry where autodiff-of-scan
materializes a dense (H, 4H) zeros+scatter every step, FIXED schedules
hoist the U gather out of the scan entirely and keep dU compact until one
final scatter, and the gate bias rides in gx (masked-dense was tried first
and measured ~0.7x of scheduled at Zaremba-large geometry on CPU — the
1/(1-p) extra FLOPs beat the saved gathers). The pallas path compiles
for the TPU and runs in interpret mode elsewhere (correct, not fast).

VMEM budget: the backward keeps U, dU and an f32 dU accumulator resident,
each (H, 4H). Zaremba-medium (H=650, f32) compiles for v5e; Zaremba-large
(H=1500, f32) exceeds a v5e core's VMEM in the backward. Beyond that the
natural extension is sharding H across cores (persistent-RNN style); not
done here. Any ``block_size`` compiles: each keep-block is padded to the
8-row tile (cell_scan.py, "Keep-block row layout"), e.g. 65 -> 72 rows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.cell_scan import CellSpec, cell_scan


def _pointwise_fwd(gates, states, *, forget_bias):
    """f32 gate nonlinearities + state update. gates order i,f,g,o."""
    (c_prev,) = states
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + forget_bias) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, (c,)


def _pointwise_bwd(gates, states_prev, states_new, dh, dstates, *,
                   forget_bias):
    """Reverse of _pointwise_fwd from pre-activation gates.

    dstates carries (dL/dc_t through c_{t+1},); dh is the total dL/dh_t.
    """
    (c_prev,), (c,) = states_prev, states_new
    (dc_in,) = dstates
    gi, gf, gg, go = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(gi)
    f = jax.nn.sigmoid(gf + forget_bias)
    g = jnp.tanh(gg)
    o = jax.nn.sigmoid(go)
    tc = jnp.tanh(c)
    do = dh * tc
    dc = dc_in + dh * o * (1.0 - tc * tc)
    dgates = jnp.concatenate([
        (dc * g) * i * (1.0 - i),
        (dc * c_prev) * f * (1.0 - f),
        (dc * i) * (1.0 - g * g),
        do * o * (1.0 - o),
    ], axis=-1)
    return dgates, (dc * f,)


@functools.lru_cache(maxsize=None)
def lstm_cell_spec(forget_bias: float = 0.0) -> CellSpec:
    """The vanilla LSTM as a cell_scan CellSpec (cached: stable jit keys)."""
    return CellSpec(
        name="lstm", num_states=1,
        pointwise_fwd=functools.partial(_pointwise_fwd,
                                        forget_bias=forget_bias),
        pointwise_bwd=functools.partial(_pointwise_bwd,
                                        forget_bias=forget_bias))


def lstm_scan(gx: jax.Array, u: jax.Array, h0: jax.Array, c0: jax.Array, *,
              keep_blocks: Optional[jax.Array] = None,
              dense_mask: Optional[jax.Array] = None,
              block_size: int = 1,
              scale: float = 1.0,
              forget_bias: float = 0.0,
              impl: str = "pallas",
              interpret: Optional[bool] = None,
              lengths: Optional[jax.Array] = None):
    """Run the full Phase-B LSTM recurrence in one fused pass.

    gx: (T, B, 4H) precomputed non-recurrent gate inputs ``x_t @ W + b``
    (Phase A of the scheduled engine, bias folded in); u: (H, 4H); h0/c0:
    (B, H). RH dropout: ``keep_blocks`` (T|1, nk) structured ids table OR
    ``dense_mask`` (T|1, B, H) random mask, with inverted-dropout
    ``scale``; a leading 1 means FIXED (one mask for all steps). Returns
    ``(hs (T, B, H), (h_fin, c_fin))`` and is differentiable w.r.t.
    (gx, u, h0, c0) through the fused reverse-time backward.

    ``lengths`` (B,) int32 makes the batch ragged: row b freezes its
    (h, c) carry after step ``lengths[b]`` and frozen steps contribute
    zero gradient — see ``cell_scan.cell_scan`` for the exact contract.

    This is the dense-recurrence (heads=1) instance of
    ``cell_scan.cell_scan``; the head axis is added/stripped here.
    """
    dm = None if dense_mask is None else dense_mask[:, :, None, :]
    hs, (h_fin, (c_fin,)) = cell_scan(
        gx[:, :, None, :], u[None], h0[:, None], (c0[:, None],),
        cell=lstm_cell_spec(float(forget_bias)),
        keep_blocks=keep_blocks, dense_mask=dm, block_size=block_size,
        scale=scale, impl=impl, interpret=interpret, lengths=lengths)
    return hs[:, :, 0], (h_fin[:, 0], c_fin[:, 0])
