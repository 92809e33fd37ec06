"""LSTM cell and stack with the paper's structured dropout (NR and RH).

The cell follows Eqs. (1)-(6): fused gate matmuls ``x@W + h@U + b`` with
W:(D,4H), U:(H,4H), gate order (i, f, g, o), then
``c' = sigmoid(f)*c + sigmoid(i)*tanh(g)``, ``h' = sigmoid(o)*tanh(c')``.

Dropout application points (Case-III: structured in batch, re-sampled per
time step):
  * NR — the non-recurrent input x_t entering W  (Zaremba'14 placement);
  * RH — the recurrent hidden h_{t-1} entering U (the paper's extension).
The cell state c is never dropped (paper §3.2). Both matmuls are
``sdrop_matmul`` calls, so FP/BP/WG all run compacted.

Three execution engines share the same numerics (tests assert equivalence):

  * ``engine="scheduled"`` (default) — the two-phase engine. Phase A
    (pre-scan): every site's masks for all T steps are sampled at once into
    ``MaskSchedule``s and each layer's NR gate matmul runs time-batched —
    one (T·B, D)@(D, 4H) compacted matmul instead of T scan-serialized
    (B, D) ones. Phase B (in-scan): the per-layer ``lax.scan`` body shrinks
    to the RH matmul + ``lstm_pointwise``, consuming precomputed gate
    slices and schedule rows threaded through as scan xs — no PRNG calls
    and no NR matmul inside the scan. Layers run as successive scans
    (cuDNN-style), which is exactly the same recurrence unrolled in a
    different order.
  * ``engine="fused"`` — same Phase A, but Phase B runs as ONE fused pass
    per layer (``kernels/lstm_scan.py``): the whole T-step recurrence in a
    single kernel with U resident across steps, per-step RH keep-block
    gathers driven by the scalar-prefetched schedule ids table, and the
    pointwise cell update fused in; a custom_vjp reverse-time kernel makes
    the backward equally fused. The Pallas kernel is the TPU path; off-TPU
    the same two-pass structure runs as an XLA masked-dense scan (the
    Pallas path still validates via interpret mode, just not fast).
  * ``engine="stepwise"`` — the reference path: one scan over time with a
    Python layer loop inside, masks drawn per step via ``ctx.state``.

Time iteration is ``jax.lax.scan`` (compact HLO, O(1) program size in T);
the fused engine replaces the Phase-B scan with the persistent kernel.

Phase A runs under the ``phase_a`` profiler scope unless its NR site runs
the Pallas gather matmul; no scope encloses Phase B, which the fused
engine's nested jit names (``launch/trace_names.py``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import layers as L
from repro.core.dropout_plan import NULL_CTX, DropoutCtx
from repro.launch import trace_names as tn

ENGINES = ("scheduled", "stepwise", "fused")


class LSTMState(NamedTuple):
    h: jax.Array   # (num_layers, B, H)
    c: jax.Array   # (num_layers, B, H)


def init_lstm_params(key, in_dim: int, hidden: int, num_layers: int,
                     *, init_scale: float = 0.05, dtype=jnp.float32):
    """Per-layer {W, U, b}; layer 0 consumes in_dim, the rest consume hidden."""
    params = []
    for l in range(num_layers):
        k1, k2, key = jax.random.split(key, 3)
        d = in_dim if l == 0 else hidden
        params.append({
            "W": L.uniform_init(k1, (d, 4 * hidden), init_scale, dtype),
            "U": L.uniform_init(k2, (hidden, 4 * hidden), init_scale, dtype),
            "b": jnp.zeros((4 * hidden,), dtype),
        })
    return params


def zero_state(num_layers: int, batch: int, hidden: int, dtype=jnp.float32) -> LSTMState:
    z = jnp.zeros((num_layers, batch, hidden), dtype)
    return LSTMState(h=z, c=z)


def lstm_pointwise(gates: jax.Array, c_prev: jax.Array, *,
                   forget_bias: float = 0.0, impl: str = "xla"):
    """Gate nonlinearities + state update. Pallas-fusable hot spot."""
    if impl == "pallas":
        from repro.kernels import ops as _kops
        return _kops.lstm_pointwise(gates, c_prev, forget_bias=forget_bias)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + forget_bias) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


def lstm_cell(params, x, h_prev, c_prev, nr_drop, rh_drop, *,
              forget_bias: float = 0.0, pointwise_impl: str = "xla"):
    """One LSTM step. nr_drop / rh_drop are DropoutStates (or None)."""
    gx = L.dense_sdrop({"w": params["W"]}, x, nr_drop)
    gh = L.dense_sdrop({"w": params["U"]}, h_prev, rh_drop)
    gates = gx + gh + params["b"]
    return lstm_pointwise(gates, c_prev, forget_bias=forget_bias,
                          impl=pointwise_impl)


def _lstm_stack_stepwise(params, x_seq, state, *, ctx, site, forget_bias,
                         pointwise_impl, lengths=None):
    """Reference engine: one scan over time, per-step mask sampling."""
    num_layers = len(params)
    hidden = state.h.shape[-1]
    batch = x_seq.shape[1]

    def step(carry, xt_t):
        hs, cs = carry
        xt, t = xt_t
        new_h, new_c = [], []
        inp = xt
        for l in range(num_layers):
            nr = ctx.state(f"{site}/layer{l}/nr", batch, inp.shape[-1], t=t)
            rh = ctx.state(f"{site}/layer{l}/rh", batch, hidden, t=t)
            h, c = lstm_cell(params[l], inp, hs[l], cs[l], nr, rh,
                             forget_bias=forget_bias,
                             pointwise_impl=pointwise_impl)
            if lengths is not None:
                act = (t < lengths)[:, None]
                h = jnp.where(act, h, hs[l])
                c = jnp.where(act, c, cs[l])
            new_h.append(h)
            new_c.append(c)
            inp = h
        return (jnp.stack(new_h), jnp.stack(new_c)), inp

    T = x_seq.shape[0]
    (h_fin, c_fin), ys = jax.lax.scan(
        step, (state.h, state.c), (x_seq, jnp.arange(T)))
    return ys, LSTMState(h=h_fin, c=c_fin)


def _lstm_stack_scheduled(params, x_seq, state, *, ctx, site, forget_bias,
                          pointwise_impl, lengths=None):
    """Two-phase engine: NR matmuls + mask sampling hoisted out of the scan.

    Layers run as successive per-layer scans: layer l's full output sequence
    (its T hidden states) is the time-batched NR input of layer l+1, so
    every layer's x@W runs as one compacted matmul over all steps. The scan
    body is RH matmul + pointwise only; its mask rows arrive as scan xs.
    """
    num_layers = len(params)
    T, batch, _ = x_seq.shape
    hidden = state.h.shape[-1]

    inp = x_seq
    h_fin, c_fin = [], []
    for l in range(num_layers):
        nr_site = f"{site}/layer{l}/nr"
        with tn.scope(tn.PHASE_A, ctx.spec(nr_site).impl):
            nr_sched = ctx.schedule(nr_site, T, batch, inp.shape[-1])
            rh_sched = ctx.schedule(f"{site}/layer{l}/rh", T, batch, hidden)
            # Phase A: time-batched NR gate matmul (no sequential
            # dependence).
            gx = L.dense_sdrop_scheduled({"w": params[l]["W"]}, inp,
                                         nr_sched)
        U, b = params[l]["U"], params[l]["b"]
        # PER_STEP masks ride through the scan as xs; FIXED/inactive ones
        # are a single state closed over as a scan constant.
        rh_xs = rh_sched.scan_rows()
        rh_const = rh_sched.state(0) if rh_xs is None else None
        ts = jnp.arange(T) if lengths is not None else None

        def step(carry, xs, _U=U, _b=b, _rh=rh_sched, _const=rh_const):
            h_prev, c_prev = carry
            gx_t, rh_row, t = xs
            st = _const if rh_row is None else _rh.state_for_row(rh_row)
            gh = L.dense_sdrop({"w": _U}, h_prev, st)
            gates = gx_t + gh + _b
            h, c = lstm_pointwise(gates, c_prev, forget_bias=forget_bias,
                                  impl=pointwise_impl)
            if lengths is not None:
                act = (t < lengths)[:, None]
                h = jnp.where(act, h, h_prev)
                c = jnp.where(act, c, c_prev)
            return (h, c), h

        (h_l, c_l), ys = jax.lax.scan(
            step, (state.h[l], state.c[l]), (gx, rh_xs, ts))
        h_fin.append(h_l)
        c_fin.append(c_l)
        inp = ys
    return inp, LSTMState(h=jnp.stack(h_fin), c=jnp.stack(c_fin))


def _lstm_stack_fused(params, x_seq, state, *, ctx, site, forget_bias,
                      pointwise_impl, lengths=None):
    """Fused engine: Phase A as in "scheduled", Phase B as ONE kernel/layer.

    Each layer's whole T-step recurrence — RH matmul (compact via the
    schedule's keep-block ids) + pointwise update — runs inside a single
    ``kernels.lstm_scan`` call with U resident across steps and a fused
    reverse-time backward (custom_vjp). The gate bias is folded into the
    time-batched Phase-A matmul, so the in-pass step is exactly
    ``gx_t + rh_t`` + pointwise. The kernel impl follows the RH site's
    ``spec.impl`` ("pallas" = persistent-scan Pallas kernel, interpret mode
    off TPU; "xla" = the same fused two-pass structure as lax.scans); when
    the RH site is inactive, ``pointwise_impl`` selects it instead.
    """
    from repro.kernels import ops as _kops

    num_layers = len(params)
    T, batch, _ = x_seq.shape
    hidden = state.h.shape[-1]

    inp = x_seq
    h_fin, c_fin = [], []
    for l in range(num_layers):
        nr_site = f"{site}/layer{l}/nr"
        with tn.scope(tn.PHASE_A, ctx.spec(nr_site).impl):
            nr_sched = ctx.schedule(nr_site, T, batch, inp.shape[-1])
            rh_sched = ctx.schedule(f"{site}/layer{l}/rh", T, batch, hidden)
            # Phase A: time-batched NR gate matmul, bias folded in.
            gx = L.dense_sdrop_scheduled(
                {"w": params[l]["W"], "b": params[l]["b"]}, inp, nr_sched)
        kw, impl = {}, pointwise_impl
        if not rh_sched.inactive:
            impl = rh_sched.spec.impl
            if rh_sched.structured:
                kw = dict(keep_blocks=rh_sched.keep_blocks,
                          block_size=rh_sched.spec.block_size,
                          scale=rh_sched.scale)
            else:
                kw = dict(dense_mask=rh_sched.dense_mask,
                          scale=rh_sched.scale)
        ys, (h_l, c_l) = _kops.lstm_scan(
            gx, params[l]["U"], state.h[l], state.c[l],
            forget_bias=forget_bias, impl=impl, lengths=lengths, **kw)
        h_fin.append(h_l)
        c_fin.append(c_l)
        inp = ys
    return inp, LSTMState(h=jnp.stack(h_fin), c=jnp.stack(c_fin))


def lstm_stack(params, x_seq: jax.Array, state: LSTMState, *,
               ctx: Optional[DropoutCtx] = None,
               site: str = "lstm",
               forget_bias: float = 0.0,
               pointwise_impl: str = "xla",
               engine: str = "scheduled",
               lengths: Optional[jax.Array] = None):
    """Run a multi-layer LSTM over a (T, B, D) sequence.

    Returns (outputs (T, B, H), final LSTMState). Dropout comes from the
    bound ``ctx``: layer ``l`` consumes sites ``{site}/layer{l}/nr`` and
    ``{site}/layer{l}/rh`` (resolved against the plan's "nr" / "rh" entries),
    with the sequence index ``t`` as the time axis — PER_STEP specs re-sample
    per step (Case-I/III), FIXED specs reuse one mask (Case-II/IV).

    ``engine`` selects the execution path (same numerics): "scheduled" =
    the two-phase engine (masks + NR matmuls hoisted out of the scan),
    "fused" = Phase B as one persistent-scan kernel per layer
    (kernels/lstm_scan.py), "stepwise" = the in-scan reference.

    ``lengths`` (B,) int32 makes the batch ragged: row b's (h, c) carries
    freeze after step ``lengths[b]`` in every layer (outputs repeat the
    last valid state, finals are the state at the last real step) and
    frozen steps contribute zero gradient — identical semantics across
    all three engines.
    """
    ctx = NULL_CTX if ctx is None else ctx
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINES}")
    run = {"scheduled": _lstm_stack_scheduled,
           "stepwise": _lstm_stack_stepwise,
           "fused": _lstm_stack_fused}[engine]
    return run(params, x_seq, state, ctx=ctx, site=site,
               forget_bias=forget_bias, pointwise_impl=pointwise_impl,
               lengths=lengths)
