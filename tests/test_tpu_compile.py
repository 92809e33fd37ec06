"""The main path's Pallas kernels compile for a TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
v5e that is described, not attached. Each test lowers a kernel at a real
width with ``interpret=False`` and checks that the compiled program holds
the Mosaic kernel (``tpu_custom_call``), so an unaligned slice, an
unlowerable op or a VMEM overflow fails here rather than on the chip.
Nothing runs, so this says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# Zaremba-medium (configs/paper_models.py): hidden 650, batch 20, unroll 35;
# block 65 divides 650, rate 0.5 keeps 5 of its 10 blocks.
LM = dict(T=35, B=20, H=650, bs=65, nk=5)
# xLSTM-1.3b sLSTM block: 4 heads of 512 (block-diagonal recurrence).
SLSTM = dict(T=32, B=8, H=4, dh=512, bs=64, nk=4)
# Luong IWSLT decoder: 2 layers x 512, input feeding, source length S.
NMT = dict(T=40, B=16, S=40, H=512, nl=2, bs=64, nk=6)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_lstm_scan_structured(one_chip, grad):
    T, B, H, bs, nk = (LM[k] for k in ("T", "B", "H", "bs", "nk"))

    def fwd(gx, u, h0, c0, kb):
        ys, _ = ops.lstm_scan(gx, u, h0, c0, keep_blocks=kb, block_size=bs,
                              scale=2.0, impl="pallas", interpret=False)
        return ys

    def grads(gx, u, h0, c0, kb):
        return jax.grad(lambda *a: (fwd(*a, kb) ** 2).sum(),
                        argnums=(0, 1, 2, 3))(gx, u, h0, c0)

    text = _compiled_text(
        grads if grad else fwd, _spec(one_chip, (T, B, 4 * H)),
        _spec(one_chip, (H, 4 * H)), _spec(one_chip, (B, H)),
        _spec(one_chip, (B, H)), _spec(one_chip, (T, nk), jnp.int32))
    assert text.count("tpu_custom_call") >= (2 if grad else 1)


def test_slstm_scan_structured_grad(one_chip):
    T, B, H, dh, bs, nk = (SLSTM[k] for k in ("T", "B", "H", "dh", "bs",
                                              "nk"))

    def loss(xg, r, h0, c0, n0, m0, kb):
        ys, _ = ops.slstm_scan(xg, r, h0, c0, n0, m0, keep_blocks=kb,
                               block_size=bs, scale=2.0, impl="pallas",
                               interpret=False)
        return (ys ** 2).sum()

    state = _spec(one_chip, (B, H, dh))
    text = _compiled_text(
        jax.grad(loss, argnums=(0, 1)), _spec(one_chip, (T, B, H, 4 * dh)),
        _spec(one_chip, (H, dh, 4 * dh)), state, state, state, state,
        _spec(one_chip, (T, nk), jnp.int32))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_decoder_scan_structured(one_chip, grad):
    T, B, S, H, nl, bs, nk = (NMT[k] for k in
                              ("T", "B", "S", "H", "nl", "bs", "nk"))
    G = 4 * H

    def fwd(gx0, us, ws, bs_, w_feed, w_comb, ep, eo, sb, h0, c0, f0, kbs):
        sites = [(kb, None, bs, 1 / 0.7) for kb in kbs]
        htil, _ = ops.decoder_scan(gx0, us, ws, bs_, w_feed, w_comb, ep, eo,
                                   sb, h0, c0, f0, sites=sites,
                                   impl="pallas", interpret=False)
        return htil

    def grads(*a):
        return jax.grad(lambda *p: (fwd(*p, a[-1]) ** 2).sum(),
                        argnums=tuple(range(12)))(*a[:-1])

    s = lambda *shape: _spec(one_chip, shape)
    text = _compiled_text(
        grads if grad else fwd, s(T, B, G), (s(H, G),) * nl,
        (s(H, G),) * (nl - 1), (s(G),) * (nl - 1), s(H, G), s(2 * H, H),
        s(B, S, H), s(B, S, H), s(B, S), s(nl, B, H), s(nl, B, H), s(B, H),
        (_spec(one_chip, (T, nk), jnp.int32),) * (2 * nl))
    assert text.count("tpu_custom_call") >= (2 if grad else 1)


@pytest.mark.parametrize("transpose_b", [False, True], ids=["fp", "bp"])
def test_gather_matmul_stepped_aligned(one_chip, transpose_b):
    T, M, K, N, bs, nk = 35, 20, 1024, 2048, 128, 4
    a = _spec(one_chip, (T, M, N) if transpose_b else (T, M, nk * bs))
    text = _compiled_text(
        lambda a, b, kb: ops.gather_matmul_stepped(
            a, b, kb, block_size=bs, a_is_compact=not transpose_b,
            transpose_b=transpose_b, interpret=False),
        a, _spec(one_chip, (K, N)), _spec(one_chip, (T, nk), jnp.int32))
    assert "tpu_custom_call" in text
