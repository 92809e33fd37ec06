"""Cell-parametric fused persistent-scan recurrence (the engine="fused" core).

PR 3 built the fused persistent-scan kernel for the vanilla LSTM cell: the
entire T-step Phase-B recurrence in ONE ``pallas_call`` (time axis = kernel
grid, carried state in VMEM scratch, recurrent weight resident via a
constant BlockSpec index_map, per-step RH keep-block gathers unrolled off
the scalar-prefetched ``(T, nk)`` MaskSchedule ids table) paired with a
``custom_vjp`` reverse-time kernel, so forward AND backward recurrent
matmuls run at (1-p) FLOPs. That machinery is cell-agnostic — only the
per-step pointwise update (gate nonlinearities + state transition) and the
set of carried states are LSTM-specific.

This module factors the split. A ``CellSpec`` supplies the cell:

  * ``num_states`` — carried cell states besides ``h`` (LSTM: 1, the cell
    state c; sLSTM: 3, the (c, n, m) cell/normalizer/stabilizer triple);
  * ``pointwise_fwd(gates, states) -> (h_new, states_new)`` — f32 gate
    nonlinearities + state update from pre-activation gates;
  * ``pointwise_bwd(gates, states_prev, states_new, dh, dstates) ->
    (dgates, dstates_prev)`` — its hand-derived reverse, from the stored
    residuals (the forward's pre-activation gates and state sequences).

Everything else — the time-as-grid pallas forward/backward kernels, the
f32 VMEM dU accumulation flushed once, the XLA two-pass ``lax.scan`` impl
with the FIXED-schedule compact-dU optimization, and the ``custom_vjp``
wiring — lives here once and is shared by every cell
(``kernels/lstm_scan.py`` and ``kernels/slstm_scan.py`` instantiate it).

Shapes are head-parametric to cover block-diagonal recurrences: the hidden
state is ``(B, H, dh)`` (H recurrence blocks a.k.a. heads, dh units each),
the recurrent weight ``u`` is ``(H, dh, G)`` with ``G`` the per-head gate
width (4*dh for both cells), and the precomputed gate inputs ``gx`` are
``(T, B, H, G)``. A dense full recurrence is the H=1 case (the LSTM);
xLSTM's sLSTM uses its per-head block-diagonal R directly. The RH mask is
over ``dh`` and shared across heads (the xlstm convention — compacted
matmul shapes stay static): ``keep_blocks`` is a ``(T|1, nk)`` ids table
of dh-blocks, ``dense_mask`` is ``(T|1, B, 1|H, dh)``. A leading 1 row is
a FIXED time pattern (one mask reused every step).

**Ragged batches** (PR 8): an optional per-row ``lengths (B,) int32``
freezes each row's carries once its sequence ends. Forward, step t of row
b with ``t >= lengths[b]`` writes ``h_{t-1}`` / ``states_{t-1}`` through
unchanged (so ``hs[t, b]`` repeats the last valid state and the returned
finals are the states at each row's last REAL step — the handoff the NMT
encoder->decoder chain and serving prefill rely on). Backward, frozen
steps route the (dh, dstates) cotangents straight through to t-1 and
contribute exactly zero dgates/dU (the pointwise VJP is linear in its
cotangents, so zeroing them at frozen steps kills the whole step's grad).
In the pallas path ``lengths`` rides as a second scalar-prefetch operand
next to the schedule-ids table; ``t < lengths`` is the per-step activity
predicate in both directions. Packed-batch loss/grads therefore equal the
per-sequence unpacked reference bit-for-bit (tests/test_ragged.py).

Dtype contract: all pointwise math and matmul accumulation run in f32;
outputs are cast back so every cotangent carries its primal's dtype
(``dgx`` -> gx.dtype, ``du`` -> u.dtype, ``dh0``/``dstates0`` -> their
states' dtypes). A bf16-gx call never silently widens its grads.

Oracles: this module is tested against the plain-``lax.scan`` references
``kernels/ref.py::lstm_scan_ref`` (via kernels/lstm_scan.py) and
``kernels/ref.py::slstm_scan_ref`` (via kernels/slstm_scan.py), with
grads checked against autodiff of those references.

The pallas path compiles for the TPU and runs in interpret mode elsewhere
(correct, not fast); ``impl="xla"`` is the CPU production path. u
(H, dh, G) must fit in VMEM beside the (B, H, ·) working set (see
kernels/lstm_scan.py). Structured mode takes any ``block_size``: see
"Keep-block row layout" below for how its gathers stay tile-aligned.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret, scan_compiler_params


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """One recurrent cell's pointwise math (see module docstring).

    Instances must be module-level constants (or lru_cached factories) so
    jit/custom_vjp caching keys stay stable across calls.
    """
    name: str
    num_states: int
    pointwise_fwd: Callable     # (gates, states) -> (h_new, states_new)
    pointwise_bwd: Callable     # (gates, st_prev, st_new, dh, dst)
                                # -> (dgates, dst_prev)


def _float0_like(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _rh_mode(kb, mask):
    if kb is not None:
        return "structured"
    if mask is not None:
        return "dense"
    return "off"


def _is_fixed(mode, kb, mask):
    return mode != "off" and (kb if mode == "structured" else mask).shape[0] == 1


def _dummy_ids():
    return jnp.zeros((1, 1), jnp.int32)


def _dummy_lens():
    return jnp.zeros((1,), jnp.int32)


def _unit_ids_table(kb, block_size):
    """(rows, nk) kept-block ids -> (rows, nk*bs) unit ids."""
    if block_size == 1:
        return kb
    offs = jnp.arange(block_size, dtype=kb.dtype)
    return (kb[..., None] * block_size + offs).reshape(kb.shape[0], -1)


# ---------------------------------------------------------------------------
# Keep-block row layout for the structured Pallas path.
#
# Mosaic slices a ref along its rows (sublanes) at a dynamic offset only
# when it can prove the offset a multiple of the row tile (8 rows of f32,
# 16 of bf16), and a value along its lanes only at multiples of 128. A
# keep-block of ``block_size`` units therefore starts at row
# ``bid * padded`` of a ref whose every block is zero-padded to
# ``padded`` rows, a multiple of the tile: the wrapper pads the weight
# (rows = hidden units) that way, and the kernel stages the hidden state
# transposed, hidden units on rows, in a scratch of the same layout. Only
# the ``block_size`` real rows of a block are ever read, so the padding
# rows never enter a product, and block ids (the (T, nk) table) keep
# their meaning.
# ---------------------------------------------------------------------------


def _padded_block(block_size, dtype):
    """``block_size`` rounded up to the row tile of ``dtype`` (>= f32's 8)."""
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return -(-block_size // tile) * tile


def _pad_blocks(w, block_size, padded):
    """(..., nb*bs, N) -> (..., nb*padded, N): zero rows after each block."""
    if padded == block_size:
        return w
    *lead, rows, n = w.shape
    nb = rows // block_size
    w = w.reshape(*lead, nb, block_size, n)
    w = jnp.pad(w, [(0, 0)] * len(lead) + [(0, 0), (0, padded - block_size),
                                            (0, 0)])
    return w.reshape(*lead, nb * padded, n)


def _unpad_blocks(w, block_size, padded):
    """Inverse of ``_pad_blocks``."""
    if padded == block_size:
        return w
    *lead, rows, n = w.shape
    nb = rows // padded
    w = w.reshape(*lead, nb, padded, n)[..., :block_size, :]
    return w.reshape(*lead, nb * block_size, n)


def _block_ds(bid, block_size, padded):
    """Row slice of the ``block_size`` real rows of keep-block ``bid``."""
    return pl.ds(pl.multiple_of(bid * padded, padded), block_size)


def _block_rows(ref, bid, block_size, padded, lead=()):
    """The real rows of keep-block ``bid`` of a block-padded ref; ``lead``
    indexes the ref's leading axes (e.g. the head)."""
    return ref[(*lead, _block_ds(bid, block_size, padded), slice(None))]


def _stage_blocks(x, ref, block_size, padded, lead=()):
    """Write x (B, W) transposed into ref: block j at rows j*padded."""
    xt = x.T
    for j in range(x.shape[1] // block_size):
        rows = slice(j * padded, j * padded + block_size)
        ref[(*lead, rows, slice(None))] = \
            xt[j * block_size:(j + 1) * block_size]


def _unstage_blocks(ref, width, block_size, padded, lead=()):
    """Read a block-staged ref back as a (B, width) value."""
    return jnp.concatenate(
        [ref[(*lead, slice(j * padded, j * padded + block_size),
              slice(None))]
         for j in range(width // block_size)], axis=0).T


def _dot_tn(a, b):
    """a.T @ b in f32, contracting the leading (row) axis of both."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T in f32, contracting the trailing (lane) axis of both."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Pallas kernels. Grid = (T,): one grid step per time step, carry in scratch.
# Variadic refs (the cell's state count is a parameter) are unpacked by
# position: [scalar ids, scalar lens | inputs | outputs | scratch]. The
# schedule-ids table AND the per-row lengths column both ride the scalar-
# prefetch path (num_scalar_prefetch=2); when the batch is rectangular the
# lens operand is a (1,) dummy and ``ragged=False`` compiles the predicate
# away entirely.
# ---------------------------------------------------------------------------


def _recurrent_fwd(gates, h_prev, u_ref, ids_ref, m_ref, stage, t, *,
                   heads, nk, block_size, padded, scale, mode, fixed):
    """Add the per-head recurrent matmul h_{t-1} @ U into ``gates``."""
    bs = block_size
    out = []
    if mode == "structured":
        for hd in range(heads):
            _stage_blocks(h_prev[:, hd], stage, bs, padded, (hd,))
            acc = jnp.zeros_like(gates[:, hd])
            for k in range(nk):                 # static unroll: exact-k masks
                bid = ids_ref[0 if fixed else t, k]
                hb = _block_rows(stage, bid, bs, padded, (hd,))   # (bs, B)
                ub = _block_rows(u_ref, bid, bs, padded, (hd,))   # (bs, G)
                acc += _dot_tn(hb, ub.astype(jnp.float32))
            out.append(gates[:, hd] + acc * scale)
    elif mode == "dense":
        hm = h_prev * m_ref[0].astype(jnp.float32) * scale
        for hd in range(heads):
            out.append(gates[:, hd] + jnp.dot(
                hm[:, hd], u_ref[hd].astype(jnp.float32),
                preferred_element_type=jnp.float32))
    else:
        for hd in range(heads):
            out.append(gates[:, hd] + jnp.dot(
                h_prev[:, hd], u_ref[hd].astype(jnp.float32),
                preferred_element_type=jnp.float32))
    return jnp.stack(out, axis=1)


def _fwd_kernel(*args, cell: CellSpec, heads: int, nk: int, block_size: int,
                padded: int, scale: float, mode: str, fixed: bool,
                ragged: bool):
    ns = cell.num_states
    ids_ref, lens_ref = args[0], args[1]
    gx_ref, u_ref, h0_ref = args[2:5]
    st0_refs = args[5:5 + ns]
    m_ref = args[5 + ns]
    hs_ref = args[6 + ns]
    gates_ref = args[7 + ns]
    stseq_refs = args[8 + ns:8 + 2 * ns]
    h_s = args[8 + 2 * ns]
    st_s = args[9 + 2 * ns:9 + 3 * ns]
    stage = args[9 + 3 * ns] if mode == "structured" else None

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)
        for s, s0 in zip(st_s, st0_refs):
            s[...] = s0[...].astype(jnp.float32)

    h_prev = h_s[...]
    gates = _recurrent_fwd(gx_ref[0].astype(jnp.float32), h_prev, u_ref,
                           ids_ref, m_ref, stage, t, heads=heads, nk=nk,
                           block_size=block_size, padded=padded, scale=scale,
                           mode=mode, fixed=fixed)
    st_prev = tuple(s[...] for s in st_s)
    h_new, st_new = cell.pointwise_fwd(gates, st_prev)
    if ragged:
        # rows past their length freeze: carry t-1's state through unchanged
        act = (t < lens_ref[...])[:, None, None]
        h_new = jnp.where(act, h_new, h_prev)
        st_new = tuple(jnp.where(act, v, p)
                       for v, p in zip(st_new, st_prev))
    h_s[...] = h_new
    for s, v in zip(st_s, st_new):
        s[...] = v
    hs_ref[0] = h_new.astype(hs_ref.dtype)
    gates_ref[0] = gates.astype(gates_ref.dtype)
    for r, v in zip(stseq_refs, st_new):
        r[0] = v.astype(r.dtype)


def _bwd_kernel(*args, cell: CellSpec, heads: int, n_steps: int, nk: int,
                block_size: int, padded: int, scale: float, mode: str,
                fixed: bool, ragged: bool):
    """Reverse-time step: grid step t processes time step r = T-1-t.

    All time-indexed refs arrive through r-indexed BlockSpecs; dU accumulates
    in f32 scratch across the whole grid and flushes on the last step.
    """
    ns = cell.num_states
    ids_ref, lens_ref = args[0], args[1]
    dy_ref, gates_ref = args[2:4]
    stn_refs = args[4:4 + ns]                  # states at t   (rev-indexed)
    stp_refs = args[4 + ns:4 + 2 * ns]         # states at t-1 (rev-indexed)
    hp_ref = args[4 + 2 * ns]
    u_ref = args[5 + 2 * ns]
    m_ref = args[6 + 2 * ns]
    dstT_refs = args[7 + 2 * ns:7 + 3 * ns]
    dgx_ref = args[7 + 3 * ns]
    du_ref = args[8 + 3 * ns]
    dh0_ref = args[9 + 3 * ns]
    dst0_refs = args[10 + 3 * ns:10 + 4 * ns]
    dh_s = args[10 + 4 * ns]
    dst_s = args[11 + 4 * ns:11 + 5 * ns]
    du_s = args[11 + 5 * ns]
    if mode == "structured":
        h_stage, dh_stage = args[12 + 5 * ns:14 + 5 * ns]

    t = pl.program_id(0)
    r = n_steps - 1 - t                      # the time step being processed

    @pl.when(t == 0)
    def _init():
        dh_s[...] = jnp.zeros_like(dh_s)
        for s, d in zip(dst_s, dstT_refs):
            s[...] = d[...].astype(jnp.float32)
        du_s[...] = jnp.zeros_like(du_s)

    dh = dy_ref[0].astype(jnp.float32) + dh_s[...]
    dst_in = tuple(s[...] for s in dst_s)
    if ragged:
        # frozen steps: zero the cotangents into the cell (-> zero dgates,
        # zero dU contribution) and pass them through to t-1 afterwards
        act = (r < lens_ref[...])[:, None, None]
        dh_c = jnp.where(act, dh, 0.0)
        dst_c = tuple(jnp.where(act, d, 0.0) for d in dst_in)
    else:
        dh_c, dst_c = dh, dst_in
    gates = gates_ref[0].astype(jnp.float32)
    st_new = tuple(s[0].astype(jnp.float32) for s in stn_refs)
    st_prev = tuple(s[0].astype(jnp.float32) for s in stp_refs)
    h_prev = hp_ref[0].astype(jnp.float32)
    dgates, dst_prev = cell.pointwise_bwd(gates, st_prev, st_new, dh_c,
                                          dst_c)
    dgx_ref[0] = dgates.astype(dgx_ref.dtype)

    bs = block_size
    dhp = []
    if mode == "structured":
        dh_width = dh.shape[-1]
        for hd in range(heads):
            dgh = dgates[:, hd]
            _stage_blocks(h_prev[:, hd], h_stage, bs, padded, (hd,))
            dh_stage[hd] = jnp.zeros(dh_stage.shape[1:], jnp.float32)
            for k in range(nk):                 # static unroll
                bid = ids_ref[0 if fixed else r, k]
                rows = _block_ds(bid, bs, padded)
                ub = _block_rows(u_ref, bid, bs, padded, (hd,)
                                 ).astype(jnp.float32)            # (bs, G)
                # BP: only the kept columns of dh_{t-1} get a contribution.
                dh_stage[hd, rows, :] = _dot_nt(ub, dgh) * scale  # (bs, B)
                # WG: compact (bs, G) product accumulated into the kept rows.
                hb = _block_rows(h_stage, bid, bs, padded, (hd,))  # (bs, B)
                du_s[hd, rows, :] = du_s[hd, rows, :] + jnp.dot(
                    hb, dgh, preferred_element_type=jnp.float32) * scale
            dhp.append(_unstage_blocks(dh_stage, dh_width, bs, padded,
                                       (hd,)))
    elif mode == "dense":
        m = m_ref[0].astype(jnp.float32)         # (B, 1|H, dh)
        for hd in range(heads):
            u_h = u_ref[hd].astype(jnp.float32)
            dgh = dgates[:, hd]
            m_h = m[:, 0] if m.shape[1] == 1 else m[:, hd]
            dhp.append(jnp.dot(dgh, u_h.T,
                               preferred_element_type=jnp.float32)
                       * m_h * scale)
            hm = h_prev[:, hd] * m_h * scale
            du_s[hd] = du_s[hd] + jnp.dot(hm.T, dgh,
                                          preferred_element_type=jnp.float32)
    else:
        for hd in range(heads):
            u_h = u_ref[hd].astype(jnp.float32)
            dgh = dgates[:, hd]
            dhp.append(jnp.dot(dgh, u_h.T,
                               preferred_element_type=jnp.float32))
            du_s[hd] = du_s[hd] + jnp.dot(h_prev[:, hd].T, dgh,
                                          preferred_element_type=jnp.float32)
    dh_prev = jnp.stack(dhp, axis=1)
    if ragged:
        dh_prev = dh_prev + jnp.where(act, 0.0, dh)
        dst_prev = tuple(p + jnp.where(act, 0.0, d)
                         for p, d in zip(dst_prev, dst_in))
    dh_s[...] = dh_prev
    for s, v in zip(dst_s, dst_prev):
        s[...] = v

    @pl.when(t == n_steps - 1)
    def _flush():
        du_ref[...] = du_s[...].astype(du_ref.dtype)
        dh0_ref[...] = dh_prev.astype(dh0_ref.dtype)
        for rf, v in zip(dst0_refs, dst_prev):
            rf[...] = v.astype(rf.dtype)


def _mask_inputs(mask, dtype, fixed, rev=None):
    """(m_in, m_spec) for the (1, B, 1|H, dh) per-step mask ref."""
    if mask is None:
        m_in = jnp.zeros((1, 1, 1, 1), dtype)        # unused placeholder
        return m_in, pl.BlockSpec((1, 1, 1, 1), lambda t, *_: (0, 0, 0, 0))
    per_t = rev if rev is not None else (lambda t, *_: (t, 0, 0, 0))
    spec = pl.BlockSpec((1, *mask.shape[1:]),
                        (lambda t, *_: (0, 0, 0, 0)) if fixed else per_t)
    return mask, spec


def _structured_layout(mode, u, batch, block_size):
    """(u as the kernel reads it, padded block rows, staging scratch).

    Structured mode pads U's keep-blocks to the row tile and stages the
    hidden state in a (H, rows, B) f32 scratch of the same layout (see
    "Keep-block row layout" above); the other modes use U as given.
    """
    if mode != "structured":
        return u, block_size, []
    padded = _padded_block(block_size, u.dtype)
    u_in = _pad_blocks(u, block_size, padded)
    heads, rows = u_in.shape[:2]
    return u_in, padded, [pltpu.VMEM((heads, rows, batch), jnp.float32)]


def _pallas_fwd(cell, gx, u, h0, states0, kb, mask, lengths, *, block_size,
                scale, interpret):
    T, B, H, G = gx.shape
    dh = u.shape[1]
    ns = cell.num_states
    mode = _rh_mode(kb, mask)
    fixed = _is_fixed(mode, kb, mask)
    ragged = lengths is not None
    nk = kb.shape[1] if mode == "structured" else 0
    ids = kb if mode == "structured" else _dummy_ids()
    lens = lengths.astype(jnp.int32) if ragged else _dummy_lens()
    m_in, m_spec = _mask_inputs(mask, gx.dtype, fixed)
    u_in, padded, stage = _structured_layout(mode, u, B, block_size)
    const3 = pl.BlockSpec((B, H, dh), lambda t, *_: (0, 0, 0))
    seq3 = pl.BlockSpec((1, B, H, dh), lambda t, *_: (t, 0, 0, 0))
    odt = h0.dtype
    kernel = functools.partial(
        _fwd_kernel, cell=cell, heads=H, nk=nk, block_size=block_size,
        padded=padded, scale=scale, mode=mode, fixed=fixed, ragged=ragged)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, B, H, G), lambda t, *_: (t, 0, 0, 0)),
                pl.BlockSpec(u_in.shape, lambda t, *_: (0, 0, 0)),  # U resident
                const3,
                *([const3] * ns),
                m_spec,
            ],
            out_specs=[
                seq3,
                pl.BlockSpec((1, B, H, G), lambda t, *_: (t, 0, 0, 0)),
                *([seq3] * ns),
            ],
            scratch_shapes=[pltpu.VMEM((B, H, dh), jnp.float32)] * (1 + ns)
            + stage,
        ),
        out_shape=[jax.ShapeDtypeStruct((T, B, H, dh), odt),
                   jax.ShapeDtypeStruct((T, B, H, G), gx.dtype),
                   *[jax.ShapeDtypeStruct((T, B, H, dh), s.dtype)
                     for s in states0]],
        compiler_params=scan_compiler_params(),
        interpret=interpret,
    )(ids, lens, gx, u_in, h0, *states0, m_in)
    hs, gates = outs[0], outs[1]
    return hs, gates, tuple(outs[2:])


def _pallas_bwd(cell, dy, dstT, gates, st_seqs, st_prev_seqs, h_prev_seq, u,
                kb, mask, lengths, *, block_size, scale, interpret):
    T, B, H, G = gates.shape
    dh = u.shape[1]
    ns = cell.num_states
    mode = _rh_mode(kb, mask)
    fixed = _is_fixed(mode, kb, mask)
    ragged = lengths is not None
    nk = kb.shape[1] if mode == "structured" else 0
    ids = kb if mode == "structured" else _dummy_ids()
    lens = lengths.astype(jnp.int32) if ragged else _dummy_lens()
    rev = lambda t, *_: (T - 1 - t, 0, 0, 0)         # reverse-time index map
    m_in, m_spec = _mask_inputs(mask, gates.dtype, fixed, rev=rev)
    u_in, padded, stage = _structured_layout(mode, u, B, block_size)
    const3 = pl.BlockSpec((B, H, dh), lambda t, *_: (0, 0, 0))
    rev3 = pl.BlockSpec((1, B, H, dh), rev)
    odt = dy.dtype
    kernel = functools.partial(
        _bwd_kernel, cell=cell, heads=H, n_steps=T, nk=nk,
        block_size=block_size, padded=padded, scale=scale, mode=mode,
        fixed=fixed, ragged=ragged)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T,),
            in_specs=[
                rev3,                                       # dy
                pl.BlockSpec((1, B, H, G), rev),            # gates
                *([rev3] * ns),                             # states at t
                *([rev3] * ns),                             # states at t-1
                rev3,                                       # h_{t-1}
                pl.BlockSpec(u_in.shape, lambda t, *_: (0, 0, 0)),  # U
                m_spec,
                *([const3] * ns),                           # d(state_T)
            ],
            out_specs=[
                pl.BlockSpec((1, B, H, G), rev),            # dgx
                pl.BlockSpec(u_in.shape, lambda t, *_: (0, 0, 0)),  # dU
                const3,                                     # dh0
                *([const3] * ns),                           # d(state_0)
            ],
            scratch_shapes=[pltpu.VMEM((B, H, dh), jnp.float32)] * (1 + ns)
            + [pltpu.VMEM(u_in.shape, jnp.float32)] + stage * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct((T, B, H, G), odt),
                   jax.ShapeDtypeStruct(u_in.shape, u.dtype),
                   jax.ShapeDtypeStruct((B, H, dh), odt),
                   *[jax.ShapeDtypeStruct((B, H, dh), odt)] * ns],
        compiler_params=scan_compiler_params(),
        interpret=interpret,
    )(ids, lens, dy, gates, *st_seqs, *st_prev_seqs, h_prev_seq, u_in,
      m_in, *dstT)
    dgx, dh0 = outs[0], outs[2]
    du = _unpad_blocks(outs[1], block_size, padded)
    return dgx, du, dh0, tuple(outs[3:])


# ---------------------------------------------------------------------------
# XLA impl: the same fused two-pass structure as lax.scans (CPU production
# path). Structured RH runs compact — per-step gathers of h columns / U rows
# by the schedule's unit ids — while random RH is masked-dense. The wins
# over "scheduled" come from the hand-written reverse-time scan: dU
# accumulates as a compact in-place scatter-add on the carry
# (autodiff-of-scan materializes a dense (H, dh, G) zeros+scatter per step
# and adds it into the carry), FIXED schedules hoist the U gather and keep
# dU compact until one final scatter, and the gate bias is prefolded into
# gx (see kernels/lstm_scan.py for the measurements behind these choices).
# ---------------------------------------------------------------------------


def _xla_fwd(cell, gx, u, h0, states0, kb, mask, lengths, *, block_size,
             scale):
    mode = _rh_mode(kb, mask)
    fixed = _is_fixed(mode, kb, mask)
    sc32 = jnp.asarray(scale, jnp.float32)
    ids = _unit_ids_table(kb, block_size) if mode == "structured" else None
    u_c0 = jnp.take(u, ids[0], axis=1) if mode == "structured" and fixed \
        else None

    xs_extra = None
    if not fixed:
        xs_extra = ids if mode == "structured" else (
            mask if mode == "dense" else None)
    ts = jnp.arange(gx.shape[0]) if lengths is not None else None

    def step(carry, xs):
        h, sts = carry
        gx_t, extra, t = xs
        if mode == "structured":
            ids_t = ids[0] if fixed else extra
            u_c = u_c0 if fixed else jnp.take(u, ids_t, axis=1)
            h_c = jnp.take(h, ids_t, axis=-1)
            r = jnp.einsum("bhk,hkg->bhg", h_c, u_c,
                           preferred_element_type=jnp.float32) * sc32
        elif mode == "dense":
            m_t = mask[0] if fixed else extra
            hm = h * m_t.astype(h.dtype) * jnp.asarray(scale, h.dtype)
            r = jnp.einsum("bhd,hdg->bhg", hm, u,
                           preferred_element_type=jnp.float32)
        else:
            r = jnp.einsum("bhd,hdg->bhg", h, u,
                           preferred_element_type=jnp.float32)
        gates = gx_t.astype(jnp.float32) + r
        h2, st2 = cell.pointwise_fwd(
            gates, tuple(s.astype(jnp.float32) for s in sts))
        h2 = h2.astype(h.dtype)
        st2 = tuple(v.astype(s.dtype) for v, s in zip(st2, sts))
        if lengths is not None:
            # rows past their length freeze: carry t-1's state through
            act = (t < lengths)[:, None, None]
            h2 = jnp.where(act, h2, h)
            st2 = tuple(jnp.where(act, v, s) for v, s in zip(st2, sts))
        return (h2, st2), (h2, st2, gates.astype(gx.dtype))

    (_, _), (hs, st_seqs, gates) = jax.lax.scan(step, (h0, states0),
                                                (gx, xs_extra, ts))
    return hs, gates, st_seqs


def _xla_bwd(cell, dy, dstT, gates, st_seqs, st_prev_seqs, h_prev_seq, u,
             kb, mask, lengths, *, block_size, scale):
    T, B, H, G = gates.shape
    dh_dim = u.shape[1]
    mode = _rh_mode(kb, mask)
    fixed = _is_fixed(mode, kb, mask)
    sc32 = jnp.asarray(scale, jnp.float32)
    ids = _unit_ids_table(kb, block_size) if mode == "structured" else None
    u_c0 = jnp.take(u, ids[0], axis=1) if mode == "structured" and fixed \
        else None
    # FIXED structured: dU stays compact (H, k, G) across the scan, one
    # scatter at the end; otherwise a full (H, dh, G) f32 accumulator.
    du0 = jnp.zeros((H, ids.shape[1], G) if mode == "structured" and fixed
                    else (H, dh_dim, G), jnp.float32)

    xs_extra = None
    if not fixed:
        xs_extra = ids if mode == "structured" else (
            mask if mode == "dense" else None)
    ts = jnp.arange(T) if lengths is not None else None

    def step(carry, xs):
        dh_next, dst_next, du = carry
        dy_t, g_t, stn_t, stp_t, hp_t, extra, t = xs
        dh = dy_t.astype(jnp.float32) + dh_next
        if lengths is not None:
            # frozen steps: zero the cotangents INTO the cell (pointwise_bwd
            # is linear in them, so dgates/du vanish for those rows) and
            # pass the originals straight through to t-1 below.
            act = (t < lengths)[:, None, None]
            dh_c = jnp.where(act, dh, 0.0)
            dst_c = tuple(jnp.where(act, d, 0.0) for d in dst_next)
        else:
            dh_c, dst_c = dh, dst_next
        dgates, dst_prev = cell.pointwise_bwd(
            g_t.astype(jnp.float32),
            tuple(s.astype(jnp.float32) for s in stp_t),
            tuple(s.astype(jnp.float32) for s in stn_t), dh_c, dst_c)
        if mode == "structured":
            ids_t = ids[0] if fixed else extra
            u_c = (u_c0 if fixed else jnp.take(u, ids_t, axis=1)
                   ).astype(jnp.float32)
            # BP: only the kept columns of dh_{t-1} get a contribution.
            dh_c = jnp.einsum("bhg,hkg->bhk", dgates, u_c,
                              preferred_element_type=jnp.float32) * sc32
            dh_prev = jnp.zeros((B, H, dh_dim), jnp.float32
                                ).at[:, :, ids_t].set(dh_c)
            # WG: compact (H, k, G) product scatter-added into the kept rows.
            h_c = jnp.take(hp_t, ids_t, axis=-1).astype(jnp.float32)
            contrib = jnp.einsum("bhk,bhg->hkg", h_c, dgates,
                                 preferred_element_type=jnp.float32) * sc32
            du = du + contrib if fixed else du.at[:, ids_t].add(contrib)
        elif mode == "dense":
            m_t = (mask[0] if fixed else extra).astype(jnp.float32)
            dh_prev = jnp.einsum("bhg,hdg->bhd", dgates,
                                 u.astype(jnp.float32),
                                 preferred_element_type=jnp.float32
                                 ) * m_t * sc32
            hm = hp_t.astype(jnp.float32) * m_t * sc32
            du = du + jnp.einsum("bhd,bhg->hdg", hm, dgates,
                                 preferred_element_type=jnp.float32)
        else:
            dh_prev = jnp.einsum("bhg,hdg->bhd", dgates,
                                 u.astype(jnp.float32),
                                 preferred_element_type=jnp.float32)
            du = du + jnp.einsum("bhd,bhg->hdg", hp_t.astype(jnp.float32),
                                 dgates, preferred_element_type=jnp.float32)
        if lengths is not None:
            dh_prev = dh_prev + jnp.where(act, 0.0, dh)
            dst_prev = tuple(p + jnp.where(act, 0.0, d)
                             for p, d in zip(dst_prev, dst_next))
        return (dh_prev, dst_prev, du), dgates.astype(dy.dtype)

    (dh0, dst0, du), dgx = jax.lax.scan(
        step,
        (jnp.zeros((B, H, dh_dim), jnp.float32),
         tuple(d.astype(jnp.float32) for d in dstT), du0),
        (dy, gates, st_seqs, st_prev_seqs, h_prev_seq, xs_extra, ts),
        reverse=True)
    if mode == "structured" and fixed:
        du = jnp.zeros((H, dh_dim, G), jnp.float32).at[:, ids[0]].set(du)
    return (dgx, du.astype(u.dtype), dh0.astype(dy.dtype),
            tuple(d.astype(dy.dtype) for d in dst0))


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _cell_scan(cell, block_size, scale, impl, interpret,
               gx, u, h0, states0, kb, mask, lengths):
    out, _ = _cell_scan_fwd(cell, block_size, scale, impl, interpret,
                            gx, u, h0, states0, kb, mask, lengths)
    return out


def _cell_scan_fwd(cell, block_size, scale, impl, interpret,
                   gx, u, h0, states0, kb, mask, lengths):
    if impl == "pallas":
        hs, gates, st_seqs = _pallas_fwd(cell, gx, u, h0, states0, kb, mask,
                                         lengths, block_size=block_size,
                                         scale=scale, interpret=interpret)
    else:
        hs, gates, st_seqs = _xla_fwd(cell, gx, u, h0, states0, kb, mask,
                                      lengths, block_size=block_size,
                                      scale=scale)
    out = (hs, hs[-1], tuple(s[-1] for s in st_seqs))
    return out, (gates, st_seqs, hs, u, h0, states0, kb, mask, lengths)


def _cell_scan_bwd(cell, block_size, scale, impl, interpret, res, dout):
    gates, st_seqs, hs, u, h0, states0, kb, mask, lengths = res
    dhs, dh_fin, dst_fin = dout
    # dL/dh_T arrives both through hs[-1] and the explicit final state.
    dy = dhs.at[-1].add(dh_fin)
    st_prev_seqs = tuple(
        jnp.concatenate([s0[None].astype(s.dtype), s[:-1]], axis=0)
        for s0, s in zip(states0, st_seqs))
    h_prev_seq = jnp.concatenate([h0[None].astype(hs.dtype), hs[:-1]], axis=0)
    if impl == "pallas":
        dgx, du, dh0, dst0 = _pallas_bwd(
            cell, dy, dst_fin, gates, st_seqs, st_prev_seqs, h_prev_seq, u,
            kb, mask, lengths, block_size=block_size, scale=scale,
            interpret=interpret)
    else:
        dgx, du, dh0, dst0 = _xla_bwd(
            cell, dy, dst_fin, gates, st_seqs, st_prev_seqs, h_prev_seq, u,
            kb, mask, lengths, block_size=block_size, scale=scale)
    dkb = None if kb is None else _float0_like(kb)
    dmask = None if mask is None else jnp.zeros_like(mask)
    dlens = None if lengths is None else _float0_like(lengths)
    # cotangents carry their primals' dtypes (gates stores gx.dtype): a
    # bf16-gx / f32-state call must not widen dgx to f32 — that doubles
    # grad memory and makes grad dtype engine-dependent.
    return (dgx.astype(gates.dtype), du.astype(u.dtype),
            dh0.astype(h0.dtype),
            tuple(d.astype(s.dtype) for d, s in zip(dst0, states0)),
            dkb, dmask, dlens)


_cell_scan.defvjp(_cell_scan_fwd, _cell_scan_bwd)


@functools.partial(jax.jit, static_argnames=(
    "cell", "block_size", "scale", "impl", "interpret"))
def cell_scan(gx: jax.Array, u: jax.Array, h0: jax.Array,
              states0: Tuple[jax.Array, ...], *,
              cell: CellSpec,
              keep_blocks: Optional[jax.Array] = None,
              dense_mask: Optional[jax.Array] = None,
              block_size: int = 1,
              scale: float = 1.0,
              impl: str = "pallas",
              interpret: Optional[bool] = None,
              lengths: Optional[jax.Array] = None):
    """Run one cell's full Phase-B recurrence in one fused pass.

    gx: (T, B, H, G) precomputed non-recurrent gate inputs (Phase A, bias
    folded in); u: (H, dh, G) per-head recurrent weights (H=1 = dense
    recurrence); h0: (B, H, dh); states0: tuple of ``cell.num_states``
    carried states, each (B, H, dh). RH dropout over the dh axis, shared
    across heads: ``keep_blocks`` (T|1, nk) structured ids table OR
    ``dense_mask`` (T|1, B, 1|H, dh) random mask, with inverted-dropout
    ``scale``; a leading 1 means FIXED (one mask for all steps). Returns
    ``(hs (T, B, H, dh), (h_fin, states_fin))`` and is differentiable
    w.r.t. (gx, u, h0, states0) through the fused reverse-time backward.

    ``lengths`` (B,) int32 makes the batch ragged: row ``b`` freezes after
    its ``lengths[b]``-th step — ``hs[t, b]`` repeats the last valid state
    for ``t >= lengths[b]``, final states are the states at the last real
    step, and frozen steps contribute exactly zero to every gradient.
    Equivalent to running each row unpacked at its own length (see
    tests/test_ragged.py); ``lengths=None`` keeps the rectangular path
    bit-identical to before.
    """
    if keep_blocks is not None and dense_mask is not None:
        raise ValueError("give at most one of keep_blocks / dense_mask")
    hs, h_fin, st_fin = _cell_scan(cell, int(block_size), float(scale),
                                   impl, resolve_interpret(interpret),
                                   gx, u, h0, tuple(states0),
                                   keep_blocks, dense_mask, lengths)
    return hs, (h_fin, st_fin)
