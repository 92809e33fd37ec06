"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU).

Every kernel variant is swept over shapes x dtypes x rates and asserted
allclose against ref.py. interpret=True executes the kernel body in Python,
so these tests validate index_map/BlockSpec logic exactly as the TPU would
see it (modulo compilation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masks
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def mk(shape, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestGatherMatmulBRows:
    """FP variant: y = a[:, kept] @ b[kept, :]."""

    @pytest.mark.parametrize("M,H,N,bs,rate", [
        (8, 64, 32, 8, 0.5),
        (16, 128, 128, 8, 0.25),
        (128, 256, 512, 128, 0.5),     # production tile sizes
        (5, 48, 17, 8, 0.5),           # unaligned M and N (padding path)
        (1, 64, 256, 8, 0.65),         # decode-like M=1
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, M, H, N, bs, rate, dtype):
        a, b = mk((M, H), dtype, 1), mk((H, N), dtype, 2)
        kb = masks.sample_keep_blocks(KEY, H, rate, bs)
        y = ops.gather_matmul(a, b, kb, block_size=bs, gather="b_rows")
        y_ref = ref.gather_matmul_ref(a, b, kb, block_size=bs, gather="b_rows")
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32), **TOL[dtype])

    def test_a_compact(self):
        M, H, N, bs, rate = 8, 64, 32, 8, 0.5
        a, b = mk((M, H), jnp.float32, 1), mk((H, N), jnp.float32, 2)
        kb = masks.sample_keep_blocks(KEY, H, rate, bs)
        ids = masks.keep_blocks_to_unit_ids(kb, bs)
        a_c = jnp.take(a, ids, axis=1)
        y = ops.gather_matmul(a_c, b, kb, block_size=bs, gather="b_rows",
                              a_is_compact=True)
        y_ref = ref.gather_matmul_ref(a, b, kb, block_size=bs, gather="b_rows")
        np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


class TestGatherMatmulBRowsT:
    """BP variant: dx_c = dy @ b[kept, :].T (compact output)."""

    @pytest.mark.parametrize("M,H,N,bs,rate", [
        (8, 64, 32, 8, 0.5),
        (16, 256, 96, 8, 0.25),
        (128, 512, 256, 128, 0.5),
        (7, 64, 33, 8, 0.5),
    ])
    def test_sweep(self, M, H, N, bs, rate):
        dy, b = mk((M, N), jnp.float32, 3), mk((H, N), jnp.float32, 4)
        kb = masks.sample_keep_blocks(KEY, H, rate, bs)
        y = ops.gather_matmul(dy, b, kb, block_size=bs, gather="b_rows",
                              transpose_b=True)
        y_ref = ref.gather_matmul_ref(dy, b, kb, block_size=bs, gather="b_rows",
                                      transpose_b=True)
        # rtol scaled for fp32 accumulation-order differences at larger K
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)


class TestGatherMatmulBCols:
    """FFN-up variant: y_c = a @ b[:, kept] (compact output)."""

    @pytest.mark.parametrize("M,K,F,bs,rate", [
        (8, 32, 64, 8, 0.5),
        (16, 96, 256, 8, 0.25),
        (128, 256, 1024, 128, 0.5),
        (6, 40, 48, 8, 0.5),
    ])
    def test_sweep(self, M, K, F, bs, rate):
        a, b = mk((M, K), jnp.float32, 5), mk((K, F), jnp.float32, 6)
        kb = masks.sample_keep_blocks(KEY, F, rate, bs)
        y = ops.gather_matmul(a, b, kb, block_size=bs, gather="b_cols")
        y_ref = ref.gather_matmul_ref(a, b, kb, block_size=bs, gather="b_cols")
        # rtol scaled for fp32 accumulation-order differences at larger K
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)


class TestGatherMatmulStepped:
    """Scheduled variant: (T, nk) ids table as extra leading grid axis."""

    @pytest.mark.parametrize("T,M,H,N,bs,rate", [
        (4, 8, 64, 32, 8, 0.5),
        (6, 16, 128, 96, 8, 0.25),
        (3, 128, 256, 256, 128, 0.5),   # production tile sizes
        (5, 7, 64, 33, 8, 0.5),         # unaligned M and N (padding path)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fp_sweep(self, T, M, H, N, bs, rate, dtype):
        a, b = mk((T, M, H), dtype, 11), mk((H, N), dtype, 12)
        kb = jnp.stack([masks.sample_keep_blocks(
            jax.random.fold_in(KEY, t), H, rate, bs) for t in range(T)])
        ids = jnp.stack([masks.keep_blocks_to_unit_ids(kb[t], bs)
                         for t in range(T)])
        a_c = jnp.take_along_axis(a, ids[:, None, :], axis=2)
        y = ops.gather_matmul_stepped(a_c, b, kb, block_size=bs,
                                      a_is_compact=True)
        y_ref = ref.gather_matmul_stepped_ref(a_c, b, kb, block_size=bs,
                                              a_is_compact=True)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32), **TOL[dtype])
        # gathering a's columns inside the kernel must agree too
        y2 = ops.gather_matmul_stepped(a, b, kb, block_size=bs)
        np.testing.assert_allclose(np.asarray(y2, np.float32),
                                   np.asarray(y_ref, np.float32), **TOL[dtype])

    @pytest.mark.parametrize("T,M,H,N,bs,rate", [
        (4, 8, 64, 32, 8, 0.5),
        (3, 16, 256, 96, 8, 0.25),
        (5, 7, 64, 33, 8, 0.5),
    ])
    def test_bp_sweep(self, T, M, H, N, bs, rate):
        dy, b = mk((T, M, N), jnp.float32, 13), mk((H, N), jnp.float32, 14)
        kb = jnp.stack([masks.sample_keep_blocks(
            jax.random.fold_in(KEY, t), H, rate, bs) for t in range(T)])
        y = ops.gather_matmul_stepped(dy, b, kb, block_size=bs,
                                      transpose_b=True)
        y_ref = ref.gather_matmul_stepped_ref(dy, b, kb, block_size=bs,
                                              transpose_b=True)
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("transpose_b", [False, True])
    def test_untileable_block_raises(self, transpose_b):
        """Compiled for TPU, a block size that is no lane multiple is
        refused at trace time, naming the size and the tile."""
        T, M, H, N, bs = 3, 4, 130, 16, 65
        kb = jnp.zeros((T, 1), jnp.int32)
        a = mk((T, M, N) if transpose_b else (T, M, bs), jnp.float32, 17)
        with pytest.raises(ValueError, match="block_size=65.*128"):
            ops.gather_matmul_stepped(a, mk((H, N), jnp.float32, 18), kb,
                                      block_size=bs, a_is_compact=True,
                                      transpose_b=transpose_b,
                                      interpret=False)

    def test_per_step_masks_differ(self):
        """Each step really contracts its own kept blocks (not step 0's)."""
        T, M, H, N, bs = 3, 4, 32, 16, 8
        a, b = mk((T, M, H), jnp.float32, 15), mk((H, N), jnp.float32, 16)
        kb = jnp.stack([masks.sample_keep_blocks(
            jax.random.fold_in(KEY, 100 + t), H, 0.5, bs) for t in range(T)])
        y = ops.gather_matmul_stepped(a, b, kb, block_size=bs)
        y0 = ops.gather_matmul_stepped(
            a, b, jnp.broadcast_to(kb[:1], kb.shape), block_size=bs)
        assert not np.allclose(np.asarray(y), np.asarray(y0))


class TestLSTMScan:
    """Fused persistent-scan recurrence vs the per-step jnp oracle.

    Sweeps RH mode (structured / random-dense / off) x time pattern
    (per-step / FIXED one-row) x impl (pallas interpret / xla), forward and
    gradients through the custom_vjp (d gx/U/h0/c0 vs autodiff-of-oracle).
    """

    def _setup(self, T, B, H, dtype=jnp.float32):
        gx = mk((T, B, 4 * H), dtype, 21) * 0.3
        u = mk((H, 4 * H), dtype, 22) * 0.1
        h0 = mk((B, H), dtype, 23) * 0.5
        c0 = mk((B, H), dtype, 24) * 0.5
        return gx, u, h0, c0

    def _kb(self, T, H, bs, rate, seed=0):
        return jnp.stack([masks.sample_keep_blocks(
            jax.random.fold_in(KEY, seed + t), H, rate, bs)
            for t in range(T)])

    def _check(self, kw, T=5, B=3, H=16, fb=0.0, dtype=jnp.float32,
               grads=True):
        gx, u, h0, c0 = self._setup(T, B, H, dtype)
        ys_ref, (hf_ref, cf_ref) = ref.lstm_scan_ref(
            gx, u, h0, c0, forget_bias=fb, **kw)
        for impl in ("xla", "pallas"):
            ys, (hf, cf) = ops.lstm_scan(gx, u, h0, c0, forget_bias=fb,
                                         impl=impl, **kw)
            np.testing.assert_allclose(
                np.asarray(ys, np.float32), np.asarray(ys_ref, np.float32),
                err_msg=f"{impl} ys", **TOL[dtype])
            np.testing.assert_allclose(
                np.asarray(cf, np.float32), np.asarray(cf_ref, np.float32),
                err_msg=f"{impl} c_fin", **TOL[dtype])
            if not grads:
                continue

            def loss(gx, u, h0, c0, impl=impl):
                ys, (hf, cf) = ops.lstm_scan(gx, u, h0, c0, forget_bias=fb,
                                             impl=impl, **kw)
                return (ys ** 2).sum() + (hf * cf).sum()

            def loss_ref(gx, u, h0, c0):
                ys, (hf, cf) = ref.lstm_scan_ref(gx, u, h0, c0,
                                                 forget_bias=fb, **kw)
                return (ys ** 2).sum() + (hf * cf).sum()

            g = jax.grad(loss, argnums=(0, 1, 2, 3))(gx, u, h0, c0)
            gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(gx, u, h0, c0)
            for a, b, nm in zip(g, gr, ("gx", "u", "h0", "c0")):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=2e-4, atol=2e-4, err_msg=f"{impl} d{nm}")

    @pytest.mark.parametrize("T,B,H,bs,rate", [
        (5, 3, 16, 4, 0.5),
        (7, 2, 32, 8, 0.25),
        (3, 4, 24, 1, 0.5),            # paper-faithful unit columns
        (4, 1, 16, 4, 0.65),           # B=1 decode-like
    ])
    def test_structured(self, T, B, H, bs, rate):
        kb = self._kb(T, H, bs, rate)
        self._check(dict(keep_blocks=kb, block_size=bs,
                         scale=masks.inverted_scale(rate, H, bs)),
                    T=T, B=B, H=H)

    def test_structured_fixed_one_row(self):
        """A (1, nk) FIXED table == the same row broadcast to all T steps."""
        T, B, H, bs = 6, 3, 16, 4
        kb = self._kb(T, H, bs, 0.5)
        kw = dict(block_size=bs, scale=2.0)
        for impl in ("xla", "pallas"):
            y1, _ = ops.lstm_scan(*self._setup(T, B, H), impl=impl,
                                  keep_blocks=kb[:1], **kw)
            y2, _ = ops.lstm_scan(*self._setup(T, B, H), impl=impl,
                                  keep_blocks=jnp.broadcast_to(
                                      kb[:1], (T, kb.shape[1])), **kw)
            np.testing.assert_allclose(y1, y2, rtol=1e-6, atol=1e-6,
                                       err_msg=impl)
        self._check(dict(keep_blocks=kb[:1], block_size=bs, scale=2.0),
                    T=T, B=B, H=H)

    @pytest.mark.parametrize("fixed", [False, True])
    def test_dense_mask(self, fixed):
        T, B, H = 5, 3, 16
        dm = (jax.random.uniform(jax.random.fold_in(KEY, 30),
                                 (1 if fixed else T, B, H)) > 0.5
              ).astype(jnp.float32)
        self._check(dict(dense_mask=dm, scale=2.0), T=T, B=B, H=H)

    @pytest.mark.parametrize("fb", [0.0, 1.0])
    def test_no_dropout(self, fb):
        self._check({}, fb=fb)

    def test_bf16(self):
        kb = self._kb(4, 16, 4, 0.5)
        self._check(dict(keep_blocks=kb, block_size=4, scale=2.0),
                    T=4, B=2, H=16, dtype=jnp.bfloat16, grads=False)

    def test_per_step_masks_differ(self):
        """Each step really gathers its own kept blocks (not step 0's)."""
        T, B, H, bs = 4, 3, 32, 8
        gx, u, h0, c0 = self._setup(T, B, H)
        kb = self._kb(T, H, bs, 0.5, seed=100)
        kw = dict(block_size=bs, scale=2.0)
        for impl in ("xla", "pallas"):
            y, _ = ops.lstm_scan(gx, u, h0, c0, impl=impl,
                                 keep_blocks=kb, **kw)
            y0, _ = ops.lstm_scan(gx, u, h0, c0, impl=impl,
                                  keep_blocks=jnp.broadcast_to(
                                      kb[:1], kb.shape), **kw)
            assert not np.allclose(np.asarray(y), np.asarray(y0)), impl

    def test_both_masks_raises(self):
        gx, u, h0, c0 = self._setup(3, 2, 16)
        kb = self._kb(3, 16, 4, 0.5)
        dm = jnp.ones((3, 2, 16))
        with pytest.raises(ValueError):
            ops.lstm_scan(gx, u, h0, c0, keep_blocks=kb, dense_mask=dm,
                          block_size=4)


class TestSLSTMScan:
    """Fused persistent-scan sLSTM vs the per-step jnp oracle.

    Mirrors TestLSTMScan over the xLSTM cell (exponential gating, (c, n, m)
    normalizer/stabilizer carries, per-head block-diagonal R): RH mode
    (structured / random-dense / off) x time pattern (per-step / FIXED
    one-row) x impl (pallas interpret / xla) x dtype, forward and gradients
    through the custom_vjp (d xg/R/h0/c0/n0/m0 vs autodiff-of-oracle).
    """

    def _setup(self, T, B, H, dh, dtype=jnp.float32, fresh=False):
        xg = mk((T, B, H, 4 * dh), dtype, 41) * 0.3
        r = mk((H, dh, 4 * dh), dtype, 42) * 0.2
        if fresh:          # canonical start: zeros + -1e30 stabilizer
            z = jnp.zeros((B, H, dh), dtype)
            return xg, r, z, z, z, jnp.full((B, H, dh), -1e30, dtype)
        h0 = mk((B, H, dh), dtype, 43) * 0.5
        c0 = mk((B, H, dh), dtype, 44) * 0.5
        n0 = jnp.abs(mk((B, H, dh), dtype, 45)) + 0.5   # mid-stream handoff
        m0 = mk((B, H, dh), dtype, 46) * 0.3
        return xg, r, h0, c0, n0, m0

    def _kb(self, T, dh, bs, rate, seed=0):
        return jnp.stack([masks.sample_keep_blocks(
            jax.random.fold_in(KEY, seed + t), dh, rate, bs)
            for t in range(T)])

    def _check(self, kw, T=5, B=2, H=3, dh=16, dtype=jnp.float32,
               grads=True, fresh=False):
        args = self._setup(T, B, H, dh, dtype, fresh=fresh)
        ys_ref, (hf_ref, (cf_ref, nf_ref, mf_ref)) = ref.slstm_scan_ref(
            *args, **kw)
        for impl in ("xla", "pallas"):
            ys, (hf, (cf, nf, mf)) = ops.slstm_scan(*args, impl=impl, **kw)
            np.testing.assert_allclose(
                np.asarray(ys, np.float32), np.asarray(ys_ref, np.float32),
                err_msg=f"{impl} ys", **TOL[dtype])
            for a, b, nm in ((cf, cf_ref, "c"), (nf, nf_ref, "n"),
                             (mf, mf_ref, "m")):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    err_msg=f"{impl} {nm}_fin", **TOL[dtype])
            if not grads:
                continue

            def loss(xg, r, h0, c0, n0, m0, impl=impl):
                ys, (hf, (cf, nf, mf)) = ops.slstm_scan(
                    xg, r, h0, c0, n0, m0, impl=impl, **kw)
                return ((ys ** 2).sum() + (hf * cf).sum()
                        + 0.1 * nf.sum() + 0.01 * mf.sum())

            def loss_ref(xg, r, h0, c0, n0, m0):
                ys, (hf, (cf, nf, mf)) = ref.slstm_scan_ref(
                    xg, r, h0, c0, n0, m0, **kw)
                return ((ys ** 2).sum() + (hf * cf).sum()
                        + 0.1 * nf.sum() + 0.01 * mf.sum())

            g = jax.grad(loss, argnums=tuple(range(6)))(*args)
            gr = jax.grad(loss_ref, argnums=tuple(range(6)))(*args)
            for a, b, nm in zip(g, gr, ("xg", "r", "h0", "c0", "n0", "m0")):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=2e-4, atol=2e-4, err_msg=f"{impl} d{nm}")

    @pytest.mark.parametrize("T,B,H,dh,bs,rate", [
        (5, 2, 3, 16, 4, 0.5),
        (7, 2, 2, 32, 8, 0.25),
        (3, 3, 4, 24, 1, 0.5),         # paper-faithful unit columns
        (4, 1, 2, 16, 4, 0.65),        # B=1 decode-like
    ])
    def test_structured(self, T, B, H, dh, bs, rate):
        kb = self._kb(T, dh, bs, rate)
        self._check(dict(keep_blocks=kb, block_size=bs,
                         scale=masks.inverted_scale(rate, dh, bs)),
                    T=T, B=B, H=H, dh=dh)

    def test_structured_fixed_one_row(self):
        """A (1, nk) FIXED table == the same row broadcast to all T steps."""
        T, B, H, dh, bs = 6, 2, 3, 16, 4
        kb = self._kb(T, dh, bs, 0.5)
        kw = dict(block_size=bs, scale=2.0)
        for impl in ("xla", "pallas"):
            y1, _ = ops.slstm_scan(*self._setup(T, B, H, dh), impl=impl,
                                   keep_blocks=kb[:1], **kw)
            y2, _ = ops.slstm_scan(*self._setup(T, B, H, dh), impl=impl,
                                   keep_blocks=jnp.broadcast_to(
                                       kb[:1], (T, kb.shape[1])), **kw)
            np.testing.assert_allclose(y1, y2, rtol=1e-6, atol=1e-6,
                                       err_msg=impl)
        self._check(dict(keep_blocks=kb[:1], block_size=bs, scale=2.0),
                    T=T, B=B, H=H, dh=dh)

    @pytest.mark.parametrize("fixed", [False, True])
    def test_dense_mask(self, fixed):
        """Case-I/II masks: (rows, B, 1, dh) shared across heads."""
        T, B, H, dh = 5, 2, 3, 16
        dm = (jax.random.uniform(jax.random.fold_in(KEY, 50),
                                 (1 if fixed else T, B, 1, dh)) > 0.5
              ).astype(jnp.float32)
        self._check(dict(dense_mask=dm, scale=2.0), T=T, B=B, H=H, dh=dh)

    def test_no_dropout(self):
        self._check({})

    def test_fresh_start(self):
        """Canonical (zeros, -1e30) init: the step-0 forget gate underflows
        to exactly 0 and the backward must stay finite (no inf*0)."""
        kb = self._kb(5, 16, 4, 0.5)
        self._check(dict(keep_blocks=kb, block_size=4, scale=2.0),
                    fresh=True)

    def test_bf16(self):
        kb = self._kb(4, 16, 4, 0.5)
        self._check(dict(keep_blocks=kb, block_size=4, scale=2.0),
                    T=4, B=2, H=2, dh=16, dtype=jnp.bfloat16, grads=False)

    def test_mixed_dtype_grad_dtypes(self):
        """bf16 xg with f32 states (the compute_dtype=bf16 model layout):
        every cotangent carries its primal's dtype — dxg must not widen
        to f32 through the custom_vjp."""
        T, B, H, dh = 3, 2, 2, 16
        xg = mk((T, B, H, 4 * dh), jnp.bfloat16, 41) * 0.3
        r = mk((H, dh, 4 * dh), jnp.float32, 42) * 0.2
        z = jnp.zeros((B, H, dh), jnp.float32)
        m0 = jnp.full((B, H, dh), -1e30, jnp.float32)
        for impl in ("xla", "pallas"):
            g = jax.grad(
                lambda *a: ops.slstm_scan(*a, impl=impl)[0]
                .astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))(
                    xg, r, z, z, z, m0)
            assert g[0].dtype == jnp.bfloat16, impl
            assert all(gi.dtype == jnp.float32 for gi in g[1:]), impl

    def test_per_step_masks_differ(self):
        """Each step really gathers its own kept blocks (not step 0's)."""
        T, B, H, dh, bs = 4, 2, 2, 32, 8
        args = self._setup(T, B, H, dh)
        kb = self._kb(T, dh, bs, 0.5, seed=100)
        kw = dict(block_size=bs, scale=2.0)
        for impl in ("xla", "pallas"):
            y, _ = ops.slstm_scan(*args, impl=impl, keep_blocks=kb, **kw)
            y0, _ = ops.slstm_scan(*args, impl=impl,
                                   keep_blocks=jnp.broadcast_to(
                                       kb[:1], kb.shape), **kw)
            assert not np.allclose(np.asarray(y), np.asarray(y0)), impl

    def test_stabilizer_extreme_gates(self):
        """Huge gate pre-activations must not overflow (the m stabilizer's
        whole job); h stays finite and |h| bounded by the output gate."""
        T, B, H, dh = 6, 2, 2, 8
        xg = jnp.full((T, B, H, 4 * dh), 40.0)
        r = mk((H, dh, 4 * dh), jnp.float32, 60) * 0.1
        z = jnp.zeros((B, H, dh))
        for impl in ("xla", "pallas"):
            ys, (hf, (cf, nf, mf)) = ops.slstm_scan(
                xg, r, z, z, z, jnp.full((B, H, dh), -1e30), impl=impl)
            assert bool(jnp.isfinite(ys).all()), impl
            assert float(jnp.abs(ys).max()) <= 1.0 + 1e-5, impl

    def test_both_masks_raises(self):
        args = self._setup(3, 2, 2, 16)
        kb = self._kb(3, 16, 4, 0.5)
        dm = jnp.ones((3, 2, 1, 16))
        with pytest.raises(ValueError):
            ops.slstm_scan(*args, keep_blocks=kb, dense_mask=dm,
                           block_size=4)


class TestDecoderScan:
    """Two-pass fused seq2seq decoder scan vs the per-step jnp oracle.

    The decoder's 2*nl in-scan dropout sites (input-feed NR / per-layer RH
    / upper-layer NR) are swept over mode (structured / random-dense / off,
    plus a mixed assignment) x time pattern (per-step / FIXED one-row) x
    impl (pallas interpret / xla): forward (h~ sequence + attention-scan
    finals h/c/feed) and gradients through the custom_vjp against
    autodiff-of-oracle, for every differentiable operand.
    """

    NL = 2
    DIFF = ("gx0", "us", "ws", "bs", "w_feed", "w_comb", "enc_proj",
            "enc_out", "h0", "c0", "feed0")

    def _args(self, T, B, S, H):
        G = 4 * H

        def m(shape, seed, scale=0.4):
            return mk(shape, jnp.float32, seed) * scale

        sb = jnp.where(jnp.arange(S) < S - 1, 0.0, -1e30)  # last src = pad
        return dict(
            gx0=m((T, B, G), 70),
            us=tuple(m((H, G), 71 + i) for i in range(self.NL)),
            ws=tuple(m((H, G), 74 + i) for i in range(self.NL - 1)),
            bs=tuple(m((G,), 77 + i) for i in range(self.NL - 1)),
            w_feed=m((H, G), 80),
            w_comb=m((2 * H, H), 81),
            enc_proj=m((B, S, H), 82),
            enc_out=m((B, S, H), 83),
            score_bias=jnp.broadcast_to(sb, (B, S)).astype(jnp.float32),
            h0=m((self.NL, B, H), 84, 0.5),
            c0=m((self.NL, B, H), 85, 0.5),
            feed0=m((B, H), 86, 0.5),
        )

    def _sites(self, kind, T, B, H, bs):
        sites = []
        for i in range(2 * self.NL):
            k = ("off", "sf", "sp", "dp")[i % 4] if kind == "mixed" else kind
            if k == "off":
                sites.append((None, None, 1, 1.0))
            elif k in ("sf", "sp"):           # structured, FIXED / per-step
                rows = 1 if k == "sf" else T
                kb = jnp.stack([masks.sample_keep_blocks(
                    jax.random.fold_in(KEY, 90 + 16 * i + t), H, 0.5, bs)
                    for t in range(rows)])
                sites.append((kb, None, bs, 2.0))
            else:                             # random-dense, FIXED / per-step
                rows = 1 if k == "df" else T
                dm = (jax.random.uniform(jax.random.fold_in(KEY, 60 + i),
                                         (rows, B, H)) > 0.5
                      ).astype(jnp.float32)
                sites.append((None, dm, 1, 2.0))
        return tuple(sites)

    def _check(self, kind, T=3, B=2, S=4, H=8, bs=4):
        args = self._args(T, B, S, H)
        sites = self._sites(kind, T, B, H, bs)
        wy = mk((T, B, H), jnp.float32, 87)
        wh = mk((self.NL, B, H), jnp.float32, 88)
        wf = mk((B, H), jnp.float32, 89)

        def loss(fn):
            def f(d):
                a = dict(args)
                a.update(d)
                htil, (hf, cf, ff) = fn(**a, sites=sites)
                return (jnp.sum(htil * wy) + jnp.sum(hf * wh)
                        + jnp.sum(cf) + jnp.sum(ff * wf))
            return f

        d0 = {k: args[k] for k in self.DIFF}
        y_ref = ref.decoder_scan_ref(**args, sites=sites)
        g_ref = jax.grad(loss(ref.decoder_scan_ref))(d0)
        for impl in ("xla", "pallas"):
            def fn(**kw):
                return ops.decoder_scan(**kw, impl=impl)

            y = fn(**args, sites=sites)
            np.testing.assert_allclose(y[0], y_ref[0], rtol=2e-5, atol=2e-5,
                                       err_msg=f"{kind}/{impl} h_tildes")
            for a, b, nm in zip(y[1], y_ref[1], ("h", "c", "feed")):
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                           err_msg=f"{kind}/{impl} {nm}_fin")
            g = jax.grad(loss(fn))(d0)
            for (p, a), (_, b) in zip(
                    jax.tree_util.tree_flatten_with_path(g)[0],
                    jax.tree_util.tree_flatten_with_path(g_ref)[0]):
                np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                           err_msg=f"{kind}/{impl} grad {p}")

    @pytest.mark.parametrize("kind", ["off", "sf", "sp", "df", "dp", "mixed"])
    def test_site_modes(self, kind):
        self._check(kind)

    def test_larger_shapes(self):
        self._check("mixed", T=5, B=3, S=6, H=16, bs=4)

    def test_structured_fixed_one_row(self):
        """A (1, nk) FIXED table == the same row broadcast to all T steps."""
        T, B, S, H, bs = 4, 2, 4, 8, 4
        args = self._args(T, B, S, H)
        kb = jnp.stack([masks.sample_keep_blocks(
            jax.random.fold_in(KEY, 200 + t), H, 0.5, bs) for t in range(T)])

        def run(impl, rows):
            sites = tuple((rows, None, bs, 2.0) for _ in range(2 * self.NL))
            return ops.decoder_scan(**args, sites=sites, impl=impl)

        for impl in ("xla", "pallas"):
            y1 = run(impl, kb[:1])
            y2 = run(impl, jnp.broadcast_to(kb[:1], (T, kb.shape[1])))
            np.testing.assert_allclose(y1[0], y2[0], rtol=1e-6, atol=1e-6,
                                       err_msg=impl)

    def test_per_step_masks_differ(self):
        """Each step really gathers its own kept blocks (not step 0's)."""
        T, B, S, H, bs = 4, 2, 4, 16, 4
        args = self._args(T, B, S, H)
        kb = jnp.stack([masks.sample_keep_blocks(
            jax.random.fold_in(KEY, 300 + t), H, 0.5, bs) for t in range(T)])

        def run(impl, rows):
            sites = ((None, None, 1, 1.0),) + tuple(
                (rows, None, bs, 2.0) for _ in range(2 * self.NL - 1))
            return ops.decoder_scan(**args, sites=sites, impl=impl)

        for impl in ("xla", "pallas"):
            y = run(impl, kb)
            y0 = run(impl, jnp.broadcast_to(kb[:1], kb.shape))
            assert not np.allclose(np.asarray(y[0]), np.asarray(y0[0])), impl

    def test_wrong_site_count_raises(self):
        args = self._args(3, 2, 4, 8)
        with pytest.raises(ValueError):
            ops.decoder_scan(**args,
                             sites=((None, None, 1, 1.0),) * (2 * self.NL - 1))


class TestLSTMPointwise:
    @pytest.mark.parametrize("B,H", [(4, 32), (8, 650), (128, 512), (3, 17)])
    @pytest.mark.parametrize("fb", [0.0, 1.0])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, H, fb, dtype):
        g, c = mk((B, 4 * H), dtype, 7), mk((B, H), dtype, 8)
        h1, c1 = ops.lstm_pointwise(g, c, forget_bias=fb)
        h2, c2 = ref.lstm_pointwise_ref(g, c, forget_bias=fb)
        np.testing.assert_allclose(np.asarray(h1, np.float32),
                                   np.asarray(h2, np.float32), **TOL[dtype])
        np.testing.assert_allclose(np.asarray(c1, np.float32),
                                   np.asarray(c2, np.float32), **TOL[dtype])

    def test_state_ranges(self):
        """sigmoid/tanh bounds: |h| <= 1 always."""
        g, c = mk((8, 256), jnp.float32, 9) * 10, mk((8, 64), jnp.float32, 10)
        h, _ = ops.lstm_pointwise(g, c)
        assert float(jnp.abs(h).max()) <= 1.0 + 1e-6


class TestKernelShardSafety:
    """Per-shard kernel calls on disjoint batch slices == the full batch.

    The shard_map data-parallel path (distributed/data_parallel.py) runs
    each fused scan on its shard's batch rows with the schedule tables
    replicated and dense masks row-sliced. That is only correct if the
    kernels carry NO cross-row state: calling them on each batch block
    independently must concatenate to the single full-batch call, forward
    AND backward (d gx blocks concatenate; dU, which every row touches,
    sums across shards because the loss is additive over rows).
    """

    def _lstm_args(self, T=5, B=8, H=16):
        gx = mk((T, B, 4 * H), jnp.float32, 401) * 0.3
        u = mk((H, 4 * H), jnp.float32, 402) * 0.1
        h0 = mk((B, H), jnp.float32, 403) * 0.5
        c0 = mk((B, H), jnp.float32, 404) * 0.5
        kb = jnp.stack([masks.sample_keep_blocks(
            jax.random.fold_in(KEY, 405 + t), H, 0.5, 4) for t in range(T)])
        dm = (jax.random.uniform(jax.random.fold_in(KEY, 406),
                                 (T, B, H)) > 0.5).astype(jnp.float32)
        lengths = jnp.array([5, 3, 0, 4, 2, 5, 1, 3], jnp.int32)
        wy = mk((T, B, H), jnp.float32, 407)
        return gx, u, h0, c0, kb, dm, lengths, wy

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    @pytest.mark.parametrize("mode", ["structured", "dense", "ragged"])
    def test_lstm_scan_shards_concat(self, impl, mode):
        T, B, H, n_shards = 5, 8, 16, 4
        gx, u, h0, c0, kb, dm, lengths, wy = self._lstm_args(T, B, H)
        kw = dict(block_size=4, scale=2.0, impl=impl)
        if mode == "structured":
            kw["keep_blocks"] = kb            # batch-independent: replicate
        elif mode == "dense":
            kw["dense_mask"] = dm             # per-row: slice with the rows
        else:
            kw["keep_blocks"] = kb
            kw["lengths"] = lengths

        def run(gx, u, h0, c0, lo, nb):
            k = dict(kw)
            if "dense_mask" in k:
                k["dense_mask"] = jax.lax.dynamic_slice_in_dim(
                    k["dense_mask"], lo, nb, 1)
            if "lengths" in k:
                k["lengths"] = jax.lax.dynamic_slice_in_dim(
                    k["lengths"], lo, nb, 0)
            return ops.lstm_scan(gx[:, lo:lo + nb], u, h0[lo:lo + nb],
                                 c0[lo:lo + nb], **k)

        ys_full, (hf_full, cf_full) = run(gx, u, h0, c0, 0, B)
        nb = B // n_shards
        parts = [run(gx, u, h0, c0, i * nb, nb) for i in range(n_shards)]
        np.testing.assert_allclose(
            np.concatenate([np.asarray(p[0]) for p in parts], axis=1),
            np.asarray(ys_full), rtol=1e-6, atol=1e-6,
            err_msg=f"{impl}/{mode} ys")
        np.testing.assert_allclose(
            np.concatenate([np.asarray(p[1][1]) for p in parts], axis=0),
            np.asarray(cf_full), rtol=1e-6, atol=1e-6,
            err_msg=f"{impl}/{mode} c_fin")

        def loss(gx, u, h0, c0, lo, nb):
            ys, (hf, cf) = run(gx, u, h0, c0, lo, nb)
            w = jax.lax.dynamic_slice_in_dim(wy, lo, nb, 1)
            return (ys * w).sum() + (hf * cf).sum()

        gf = jax.grad(loss, argnums=(0, 1))(gx, u, h0, c0, 0, B)
        gs = [jax.grad(loss, argnums=(0, 1))(gx, u, h0, c0, i * nb, nb)
              for i in range(n_shards)]
        # d gx: each shard only touches its rows -> the blocks sum to full
        np.testing.assert_allclose(
            np.asarray(sum(g[0] for g in gs)), np.asarray(gf[0]),
            rtol=2e-5, atol=2e-5, err_msg=f"{impl}/{mode} dgx")
        # dU: every shard contributes; the psum equals the full-batch grad
        np.testing.assert_allclose(
            np.asarray(sum(g[1] for g in gs)), np.asarray(gf[1]),
            rtol=2e-5, atol=2e-5, err_msg=f"{impl}/{mode} dU")

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_decoder_scan_shards_concat(self, impl):
        """decoder_scan (attention + input feeding in-scan): disjoint
        batch-block calls — enc memory, score_bias, initial states and
        the sites' dense masks all row-sliced — concatenate to the
        full-batch call, fwd + bwd."""
        T, B, S, H, bs, NL, n_shards = 3, 4, 4, 8, 4, 2, 2
        dec = TestDecoderScan()
        args = dec._args(T, B, S, H)
        sites = dec._sites("mixed", T, B, H, bs)
        wy = mk((T, B, H), jnp.float32, 410)

        def shard_args(a, st, lo, nb):
            a = dict(a)
            for k in ("enc_proj", "enc_out", "score_bias", "feed0"):
                a[k] = a[k][lo:lo + nb]
            a["gx0"] = a["gx0"][:, lo:lo + nb]
            a["h0"] = a["h0"][:, lo:lo + nb]
            a["c0"] = a["c0"][:, lo:lo + nb]
            st = tuple((kb, None if dm is None else dm[:, lo:lo + nb], b, s)
                       for kb, dm, b, s in st)
            return a, st

        def run(a, st, lo, nb):
            a, st = shard_args(a, st, lo, nb)
            return ops.decoder_scan(**a, sites=st, impl=impl)

        y_full = run(args, sites, 0, B)
        nb = B // n_shards
        parts = [run(args, sites, i * nb, nb) for i in range(n_shards)]
        np.testing.assert_allclose(
            np.concatenate([np.asarray(p[0]) for p in parts], axis=1),
            np.asarray(y_full[0]), rtol=1e-6, atol=1e-6,
            err_msg=f"{impl} h_tildes")
        for j, nm in zip(range(3), ("h", "c", "feed")):
            ax = 0 if nm == "feed" else 1
            np.testing.assert_allclose(
                np.concatenate([np.asarray(p[1][j]) for p in parts],
                               axis=ax),
                np.asarray(y_full[1][j]), rtol=1e-6, atol=1e-6,
                err_msg=f"{impl} {nm}_fin")

        diff = ("gx0", "us", "w_feed", "w_comb")

        def loss(d, lo, nb):
            a = dict(args)
            a.update(d)
            a, st = shard_args(a, sites, lo, nb)
            htil, (hf, cf, ff) = ops.decoder_scan(**a, sites=st, impl=impl)
            w = jax.lax.dynamic_slice_in_dim(wy, lo, nb, 1)
            return (htil * w).sum() + (hf * cf).sum() + ff.sum()

        d0 = {k: args[k] for k in diff}
        gf = jax.grad(loss)(d0, 0, B)
        gs = [jax.grad(loss)(d0, i * nb, nb) for i in range(n_shards)]
        for (p, a), *rest in zip(
                jax.tree_util.tree_flatten_with_path(gf)[0],
                *(jax.tree_util.tree_flatten_with_path(g)[0] for g in gs)):
            summed = sum(np.asarray(r[1]) for r in rest)
            np.testing.assert_allclose(summed, np.asarray(a),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"{impl} grad {p}")
