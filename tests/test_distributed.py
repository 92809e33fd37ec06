"""Sharded-vs-single-device equivalence for the training engines.

The shard_map data-parallel path (distributed/data_parallel.py wired
through launch/steps.py::make_sharded_loss_and_grad) must reproduce the
single-device loss AND gradients — allclose at f32 — for every recurrent
family x engine x dropout case, because:

  * structured keep-block tables are batch-independent: every shard
    resamples the identical table from the same site key (replication for
    free);
  * dense per-row bitmasks sample the GLOBAL mask and row-slice, so each
    shard sees bit-identical rows to the unsharded run
    (core/dropout_plan.py "Batch sharding", DropoutCtx + BatchShard);
  * losses combine as exact weighted means — psum(loss_i * w_i) /
    max(psum(w_i), 1) — so ragged batches (clamped denominators, all-pad
    shards) agree too, not just rectangular ones.

Multi-device tests take the module-scoped ``host_devices`` fixture
(conftest.py) and SKIP on a 1-device host; CI's distributed job runs them
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``. Property
tests follow the test_engine.py convention: hypothesis when installed,
a deterministic mini-grid either way.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

try:
    import hypothesis
    from hypothesis import given, settings
    from hypothesis import strategies as hst
except ImportError:      # pragma: no cover
    hypothesis = None

from repro.configs import adapters
from repro.core.dropout_plan import BatchShard, DropoutPlan
from repro.data import synthetic
from repro.distributed import data_parallel as dp
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.models import lstm_lm, seq2seq, tagger, xlstm

KEY = jax.random.PRNGKey(0)
DROP_KEY = jax.random.PRNGKey(7)
ENGINES = ("stepwise", "scheduled", "fused")
CASES = ("case1", "case2", "case3", "case4")


def _bs(case):
    return 4 if case in ("case3", "case4") else 1


# ---------------------------------------------------------------------------
# tiny model cells (one per recurrent family)
# ---------------------------------------------------------------------------


def _lm_cell(case, engine, rate=0.5):
    plan = DropoutPlan.case(case, rate, block_size=_bs(case),
                            sites=("embed", "nr", "rh", "out"))
    cfg = lstm_lm.LSTMLMConfig(vocab=50, embed=16, hidden=16, num_layers=2,
                               plan=plan, engine=engine)
    params = lstm_lm.init_params(KEY, cfg)
    batch = {"tokens": jax.random.randint(KEY, (8, 6), 0, 50),
             "labels": jax.random.randint(KEY, (8, 6), 0, 50)}
    return "lstm_lm", cfg, lstm_lm.loss_fn, params, batch


def _nmt_cell(case, engine, rate=0.3):
    plan = DropoutPlan.case(case, rate, block_size=_bs(case),
                            sites=("nr", "rh", "out"))
    cfg = seq2seq.NMTConfig(src_vocab=30, tgt_vocab=30, embed=12, hidden=12,
                            num_layers=2, plan=plan, engine=engine)
    params = seq2seq.init_params(KEY, cfg)
    batch = jax.tree.map(jnp.asarray,
                         synthetic.nmt_pairs(8, 30, 30, max_len=10, seed=3))
    return "nmt", cfg, seq2seq.loss_fn, params, batch


def _tagger_cell(case, engine, rate=0.5):
    plan = DropoutPlan.case(case, rate, block_size=_bs(case),
                            sites=("inp", "rh"))
    cfg = tagger.TaggerConfig(vocab=30, char_vocab=20, hidden=16, num_tags=5,
                              word_embed=12, char_filters=8, plan=plan,
                              engine=engine)
    params = tagger.init_params(KEY, cfg)
    batch = jax.tree.map(jnp.asarray, synthetic.ner_examples(
        8, 30, 20, 5, seq=7, seed=5))
    return "tagger", cfg, tagger.loss_fn, params, batch


def _xlstm_cell(case, engine, rate=0.5):
    plan = DropoutPlan.case(case, rate, block_size=_bs(case),
                            sites=("nr", "rh"))
    cfg = xlstm.XLSTMConfig(num_layers=2, d_model=32, n_heads=4, vocab=40,
                            chunk=4, slstm_every=1, plan=plan, engine=engine)
    params = shd.strip(xlstm.init_params(KEY, cfg))
    tok = jax.random.randint(KEY, (8, 8), 0, 40)
    return "xlstm", cfg, xlstm.loss_fn, params, {"tokens": tok,
                                                 "labels": tok}


_CELLS = {"lstm_lm": _lm_cell, "nmt": _nmt_cell, "tagger": _tagger_cell,
          "xlstm": _xlstm_cell}


# ---------------------------------------------------------------------------
# the equivalence check itself
# ---------------------------------------------------------------------------


def _check_sharded(kind, cfg, lfn, params, batch, d, *, step=1,
                   rtol=5e-4, atol=1e-5, with_rules=False):
    """Sharded (d devices) loss/grads == single-device loss/grads.
    ``with_rules`` passes the mesh's sharding rules into the shard_map body,
    as ``launch/train.py --mesh`` does."""
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: lfn(p, batch, cfg, drop_key=DROP_KEY, step=step))(params)
    mesh = mesh_mod.make_data_mesh(d)
    rules = shd.rules_for_mesh(mesh) if with_rules else None
    vag = steps_mod.make_sharded_loss_and_grad(kind, cfg, mesh, rules=rules)
    loss, grads = jax.jit(vag)(params, batch, step, DROP_KEY)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5,
                               err_msg=f"{kind} d={d} loss")
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_flatten_with_path(ref_grads)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"{kind} d={d} grad {path}")


def _cap(host_devices, d=4):
    return min(d, host_devices)


# ---------------------------------------------------------------------------
# engine x case matrix
# ---------------------------------------------------------------------------


class TestShardedEquivalence:
    """All four families, all three engines, sharded == single-device."""

    @pytest.mark.parametrize("case", CASES)
    @pytest.mark.parametrize("engine", ENGINES)
    def test_lstm_lm(self, host_devices, case, engine):
        _check_sharded(*_lm_cell(case, engine), _cap(host_devices))

    @pytest.mark.parametrize("case", ("case1", "case3"))
    @pytest.mark.parametrize("engine", ENGINES)
    def test_nmt(self, host_devices, case, engine):
        _check_sharded(*_nmt_cell(case, engine), _cap(host_devices))

    @pytest.mark.parametrize("case", ("case1", "case3"))
    @pytest.mark.parametrize("engine", ENGINES)
    def test_tagger(self, host_devices, case, engine):
        _check_sharded(*_tagger_cell(case, engine), _cap(host_devices))

    @pytest.mark.parametrize("case", ("case1", "case3"))
    @pytest.mark.parametrize("engine", ENGINES)
    def test_xlstm(self, host_devices, case, engine):
        _check_sharded(*_xlstm_cell(case, engine), _cap(host_devices))

    @pytest.mark.parametrize("d", (1, 2, 4))
    def test_xlstm_with_rules(self, d):
        """xlstm's shard_act runs inside the shard_map body, where the data
        axis is Manual; a per-shard batch that divides by the axis size
        must train, not trip with_sharding_constraint."""
        if d > len(jax.devices()):
            pytest.skip(f"needs {d} host devices")
        kind, cfg, lfn, params, _ = _xlstm_cell("case3", "fused")
        tok = jax.random.randint(KEY, (d * d * 2, 8), 0, 40)
        _check_sharded(kind, cfg, lfn, params,
                       {"tokens": tok, "labels": tok}, d, with_rules=True)

    def test_fixed_time_pattern_per_family(self, host_devices):
        """case2 (RANDOM x FIXED) on the remaining families: one dense
        mask per bind, row-sliced identically on every shard + step."""
        for cell in (_nmt_cell, _tagger_cell, _xlstm_cell):
            _check_sharded(*cell("case2", "fused"), _cap(host_devices))

    def test_device_sweep_fused_case3(self, host_devices):
        """The acceptance geometry: fused engine, active case3, every
        power-of-two device count this host offers."""
        for d in (1, 2, 4, 8):
            if d <= host_devices:
                _check_sharded(*_lm_cell("case3", "fused"), d)

    def test_train_step_parity(self, host_devices):
        """One full sharded optimizer step == the unsharded train step
        (params and loss after update, not just the gradients)."""
        from repro import optim
        kind, cfg, lfn, params, batch = _lm_cell("case3", "fused")
        opt = optim.chain(optim.clip_by_global_norm(1.0), optim.adamw(1e-3))
        mesh = mesh_mod.make_data_mesh(_cap(host_devices))
        sharded = steps_mod.make_sharded_train_step(kind, cfg, opt, mesh)

        def ref_step(p, o, b, step, key):
            loss, grads = jax.value_and_grad(
                lambda q: lfn(q, b, cfg, drop_key=key, step=step))(p)
            updates, o = opt.update(grads, o, p)
            return optim.apply_updates(p, updates), o, loss

        o0 = opt.init(params)
        p_ref, _, l_ref = jax.jit(ref_step)(params, o0, batch, 1, DROP_KEY)
        p_sh, _, l_sh = jax.jit(sharded)(params, opt.init(params), batch, 1,
                                         DROP_KEY)
        np.testing.assert_allclose(float(l_sh), float(l_ref), rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5),
            p_sh, p_ref)


class TestRaggedSharded:
    """Length-column batches: clamped masked-mean denominators, dummy
    (length-0) rows, and the in-kernel carry freeze all survive sharding."""

    def test_lstm_lm_ragged(self, host_devices):
        kind, cfg, lfn, params, batch = _lm_cell("case3", "fused")
        batch = dict(batch)
        batch["lengths"] = jnp.array([6, 3, 0, 5, 2, 6, 1, 4], jnp.int32)
        _check_sharded(kind, cfg, lfn, params, batch, _cap(host_devices))

    def test_lstm_lm_ragged_dense_case(self, host_devices):
        kind, cfg, lfn, params, batch = _lm_cell("case1", "scheduled")
        batch = dict(batch)
        batch["lengths"] = jnp.array([6, 3, 0, 5, 2, 6, 1, 4], jnp.int32)
        _check_sharded(kind, cfg, lfn, params, batch, _cap(host_devices))

    def test_nmt_ragged(self, host_devices):
        kind, cfg, lfn, params, batch = _nmt_cell("case3", "fused")
        batch = dict(batch)
        S = batch["src"].shape[1]
        batch.pop("src_mask", None)
        batch.pop("tgt_mask", None)
        batch["src_lengths"] = jnp.array([S, 4, 2, S, 5, 3, 6, 1], jnp.int32)
        batch["tgt_lengths"] = jnp.array([6, 3, 2, S, 4, 2, 5, 1], jnp.int32)
        _check_sharded(kind, cfg, lfn, params, batch, _cap(host_devices))

    def test_tagger_ragged(self, host_devices):
        kind, cfg, lfn, params, batch = _tagger_cell("case3", "fused")
        batch = dict(batch)
        lengths = jnp.array([7, 3, 0, 5, 2, 7, 1, 4], jnp.int32)
        batch["lengths"] = lengths
        batch["mask"] = (jnp.arange(7)[None, :] < lengths[:, None])
        _check_sharded(kind, cfg, lfn, params, batch, _cap(host_devices))

    def test_xlstm_ragged(self, host_devices):
        kind, cfg, lfn, params, batch = _xlstm_cell("case3", "fused")
        batch = dict(batch)
        batch["lengths"] = jnp.array([8, 3, 0, 5, 2, 8, 1, 4], jnp.int32)
        _check_sharded(kind, cfg, lfn, params, batch, _cap(host_devices))

    def test_all_pad_shard(self, host_devices):
        """A shard of nothing but dummy rows (w_i = 0) contributes zero,
        not NaN — the clamp identity l_i * w_i = masked-sum holds."""
        d = _cap(host_devices, 2)
        kind, cfg, lfn, params, batch = _lm_cell("case3", "fused")
        batch = dict(batch)
        # rows are split into d contiguous blocks; zero out the last block
        lengths = np.array([6, 3, 4, 5, 2, 6, 1, 4], np.int32)
        lengths[-(8 // d):] = 0
        batch["lengths"] = jnp.asarray(lengths)
        _check_sharded(kind, cfg, lfn, params, batch, d)


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------


class TestGuards:
    def test_non_divisible_batch_raises(self, host_devices):
        d = _cap(host_devices, 4)
        kind, cfg, lfn, params, _ = _lm_cell("case3", "fused")
        mesh = mesh_mod.make_data_mesh(d)
        vag = steps_mod.make_sharded_loss_and_grad(kind, cfg, mesh)
        bad = {"tokens": jnp.zeros((d + 1, 5), jnp.int32),
               "labels": jnp.zeros((d + 1, 5), jnp.int32)}
        with pytest.raises(ValueError, match="divisible"):
            vag(params, bad, 0, DROP_KEY)

    def test_non_divisible_batch_raises_jitted(self, host_devices):
        """The guard fires at trace time too (shapes are static), so the
        jitted path gets the same message, not an XLA reshape error."""
        d = _cap(host_devices, 4)
        kind, cfg, lfn, params, _ = _lm_cell("case3", "fused")
        mesh = mesh_mod.make_data_mesh(d)
        vag = jax.jit(steps_mod.make_sharded_loss_and_grad(kind, cfg, mesh))
        bad = {"tokens": jnp.zeros((d + 1, 5), jnp.int32),
               "labels": jnp.zeros((d + 1, 5), jnp.int32)}
        with pytest.raises(ValueError, match="divisible"):
            vag(params, bad, 0, DROP_KEY)

    def test_unsupported_kind_raises(self):
        mesh = mesh_mod.make_host_mesh()
        cfg = object()
        with pytest.raises(ValueError, match="sharded train path"):
            steps_mod.make_sharded_loss_and_grad("transformer", cfg, mesh)

    def test_loss_weight_unknown_kind(self):
        with pytest.raises(ValueError, match="sharded-loss weight"):
            adapters.loss_weight("ssm")

    def test_batch_shard_validates_count(self):
        with pytest.raises(ValueError, match="shard count"):
            BatchShard(index=0, count=0)

    def test_mesh_size_out_of_range(self):
        with pytest.raises(ValueError, match="out of range"):
            mesh_mod.make_data_mesh(len(jax.devices()) + 1)

    def test_shard_put_replicate_fallback(self, host_devices):
        """distributed/sharding.py shard_put: a param dim NOT divisible by
        its mesh axis falls back to replication instead of erroring."""
        d = _cap(host_devices, 2)
        mesh = mesh_mod.make_data_mesh(d)
        rules = shd.rules_for_mesh(mesh)
        odd = jnp.arange(d * 3 + 1, dtype=jnp.float32)[:, None] * jnp.ones(4)
        out = shd.shard_put({"w": odd}, {"w": ("batch", None)}, rules, mesh)
        # non-divisible dim 0 -> replicated spec, value untouched
        spec = out["w"].sharding.spec
        assert all(ax is None for ax in spec), spec
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(odd))
        # sanity: the divisible twin DOES shard over the data axis
        even = jnp.ones((d * 4, 4), jnp.float32)
        out2 = shd.shard_put({"w": even}, {"w": ("batch", None)}, rules, mesh)
        spec0 = out2["w"].sharding.spec[0]
        flat = spec0 if isinstance(spec0, tuple) else (spec0,)
        assert "data" in flat, out2["w"].sharding.spec

    def test_meshes_are_auto_typed(self):
        """The model code leaves sharding to propagation, which Explicit
        axes (``jax.make_mesh``'s default) refuse."""
        auto = (jax.sharding.AxisType.Auto,) * 2
        assert mesh_mod.make_host_mesh().axis_types == auto
        assert mesh_mod.make_data_mesh(1).axis_types == auto

    def test_shard_act_follows_the_set_mesh(self):
        """shard_act constrains under ``jax.set_mesh`` and is the identity
        without a mesh."""
        mesh = mesh_mod.make_host_mesh()
        rules = shd.rules_for_mesh(mesh)
        x = jnp.ones((4, 8), jnp.float32)

        def f(x):
            return shd.shard_act(x, ("batch", None), rules) * 2

        assert "sharding_constraint" not in jax.jit(f).lower(x).as_text()
        with jax.set_mesh(mesh):
            text = jax.jit(f).lower(x).as_text()
            y = jax.jit(f)(x)
        assert "sharding_constraint" in text, text
        np.testing.assert_array_equal(np.asarray(y), 2 * np.ones((4, 8)))

    def test_shard_act_skips_manual_axes(self):
        """Inside shard_map over "data" only the Auto "model" axis is
        constrained; over every axis shard_act is the identity."""
        mesh = mesh_mod.make_host_mesh()
        rules = shd.rules_for_mesh(mesh)
        x = jnp.ones((4, 8), jnp.float32)

        def body(x):
            return shd.shard_act(x, ("batch", "mlp"), rules) * 2

        def lowered(axis_names):
            f = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), axis_names=axis_names)
            return jax.jit(f).lower(x).as_text()

        partial = [l for l in lowered({"data"}).splitlines()
                   if "sharding_constraint" in l and "model" in l]
        assert len(partial) == 1 and '"data"' not in partial[0], partial
        assert "sharding_constraint %arg" not in lowered({"data", "model"})

    def test_weight_matches_unsharded_denominator(self):
        """loss_weight(kind) returns exactly the weight the unsharded loss
        divides by: loss * weight is additive across row blocks."""
        for kind in adapters.SHARD_KINDS:
            _, cfg, lfn, params, batch = _CELLS[kind]("case3", "scheduled")
            w = adapters.loss_weight(kind)
            full = (float(lfn(params, batch, cfg, drop_key=None, step=0))
                    * float(w(batch, cfg)))
            B = batch["src" if kind == "nmt" else
                      "words" if kind == "tagger" else "tokens"].shape[0]
            halves = 0.0
            for lo, hi in ((0, B // 2), (B // 2, B)):
                part = {k: (v[lo:hi] if getattr(v, "ndim", 0) >= 1 else v)
                        for k, v in batch.items()}
                halves += (float(lfn(params, part, cfg, drop_key=None,
                                     step=0)) * float(w(part, cfg)))
            np.testing.assert_allclose(halves, full, rtol=1e-5)


# ---------------------------------------------------------------------------
# property tests (hypothesis + deterministic fallback, test_engine.py style)
# ---------------------------------------------------------------------------


def _check_property(d, B, T, rate, case, seed, host_devices):
    d = min(d, host_devices)
    B = B - (B % d)   # keep the draw divisible
    plan = DropoutPlan.case(case, rate, block_size=_bs(case),
                            sites=("embed", "nr", "rh", "out"))
    cfg = lstm_lm.LSTMLMConfig(vocab=40, embed=16, hidden=16, num_layers=2,
                               plan=plan, engine="fused")
    k = jax.random.PRNGKey(seed)
    params = lstm_lm.init_params(k, cfg)
    batch = {"tokens": jax.random.randint(k, (B, T), 0, 40),
             "labels": jax.random.randint(k, (B, T), 0, 40)}
    _check_sharded("lstm_lm", cfg, lstm_lm.loss_fn, params, batch, d,
                   step=seed % 5)


def test_property_grid(host_devices):
    """Deterministic mini-grid through the same check the hypothesis
    property runs (coverage even where hypothesis is not installed)."""
    _check_property(d=2, B=4, T=5, rate=0.5, case="case3", seed=11,
                    host_devices=host_devices)
    _check_property(d=4, B=8, T=3, rate=0.25, case="case1", seed=12,
                    host_devices=host_devices)
    _check_property(d=8, B=8, T=4, rate=0.65, case="case2", seed=13,
                    host_devices=host_devices)


if hypothesis is not None:
    class TestDistributedProperties:
        @settings(max_examples=6, deadline=None)
        @given(d=hst.sampled_from((1, 2, 4, 8)),
               B=hst.sampled_from((8, 16)),
               T=hst.sampled_from((2, 5)),
               rate=hst.sampled_from((0.25, 0.5, 0.65)),
               case=hst.sampled_from(CASES),
               seed=hst.integers(0, 2 ** 16))
        def test_sharded_equivalence(self, host_devices, d, B, T, rate,
                                     case, seed):
            _check_property(d, B, T, rate, case, seed, host_devices)
else:                                          # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_distributed_properties():
        pass
