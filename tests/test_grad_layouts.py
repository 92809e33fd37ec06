"""The train step's gradient layout pins (``steps.pin_grad_layouts``) on
the CPU, where every default layout is row-major: nothing is pinned, and
the step compiles and trains exactly as it would without the pins.
Their effect on a v5e is checked in ``test_tpu_compile.py``.
"""
import jax
import numpy as np
import pytest

from repro import configs, optim
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_mod
from repro.launch import steps
from repro.launch import train


@pytest.fixture(scope="module")
def lm():
    """A smoke-size lstm_lm: (step fn, params, opt, batches)."""
    spec = configs.get_arch("zaremba-medium")
    cfg = spec.smoke()
    mesh = mesh_mod.make_host_mesh()
    rules = shd.rules_for_mesh(mesh)
    init_fn, _, _, _ = steps.param_setup(spec, cfg, mesh, rules)
    opt = optim.chain(optim.clip_by_global_norm(1.0), optim.adamw(1e-3))
    batch_fn = train.make_batch_fn(spec, cfg, 2, 8, 0)
    return (steps.make_train_step(spec, cfg, opt, rules), init_fn(), opt,
            [batch_fn(i) for i in range(2)])


def _args(lm, i):
    return (lm[3][i], jax.numpy.int32(i),
            jax.random.fold_in(jax.random.PRNGKey(0), i))


def _unpinned(monkeypatch):
    monkeypatch.setattr(steps, "pin_grad_layouts", lambda g: g)


def test_nothing_is_pinned_on_cpu(lm):
    pins, n, nbytes = steps.grad_layout_pins(lm[1])
    assert len(pins) == len(jax.tree.leaves(lm[1]))
    assert all(lay is None for lay in pins)
    assert (n, nbytes) == (0, 0)


def test_cpu_step_compiles_the_same_without_the_pins(lm, monkeypatch):
    fn, params, opt, _ = lm

    def text():
        jax.clear_caches()
        return jax.jit(fn, donate_argnums=(0, 1)).lower(
            params, opt.init(params), *_args(lm, 0)).compile().as_text()

    texts = []
    for pins in (True, False):      # one call site: the same op metadata
        if not pins:
            _unpinned(monkeypatch)
        texts.append(text())
    assert texts[0] == texts[1]


def test_two_steps_are_bit_identical_without_the_pins(lm, monkeypatch):
    fn, params, opt, _ = lm

    def two_steps():
        jax.clear_caches()
        step = jax.jit(fn)
        p, o = params, opt.init(params)
        for i in range(2):
            p, o, _ = step(p, o, *_args(lm, i))
        return p, o

    pinned = two_steps()
    _unpinned(monkeypatch)
    plain = two_steps()
    assert jax.tree.structure(pinned) == jax.tree.structure(plain)
    for a, b in zip(jax.tree.leaves(pinned), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
