"""Cells of architectures beyond the LSTM's: the harness builds the
program's step for an arch whose config has no recurrent ``engine``
(``zamba2-1.2b``, kind ``ssm``) and for one that has it (``xlstm-1.3b``),
from a cell held in memory at the arch's smoke sizes with its dtypes
named ``"float32"``, and one step runs. A cell that names an engine its
arch lacks is refused with a message that names the cell and the field.
"""
import dataclasses
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from repro import configs  # noqa: E402
from repro.configs import adapters  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402

PLAN = {"nr": {"case": "case3", "rate": 0.25, "block": 8, "impl": "xla"}}


def _cell(arch, engine=None):
    """A cell of ``arch`` at its smoke sizes. Its configuration module is
    a stub that makes the program's own (stripped) parameter tree."""
    spec = configs.get_arch(arch)
    smoke, full = spec.smoke(), spec.full()
    sizes = {f.name: getattr(smoke, f.name)
             for f in dataclasses.fields(smoke)
             if f.name not in ("plan", "engine")
             and getattr(smoke, f.name) != getattr(full, f.name)}
    sizes.update(param_dtype="float32", compute_dtype="float32")
    cell_spec = {"plan": PLAN}
    if engine:
        cell_spec["engine"] = engine
    cell = harness.Cell(f"{arch}.smoke", cell_spec,
                        {"name": arch, "arch": arch, "sizes": sizes,
                         "init_scale": None}, None)

    def init_weights(key, sizes, scale, dtype):
        spec, cfg = harness.program_config(cell)
        tree = shd.strip(adapters.init_params(spec.kind, key, cfg))
        return jax.tree.map(lambda x: x.astype(dtype), tree)

    cell.mod = types.SimpleNamespace(init_weights=init_weights)
    return cell


@pytest.mark.parametrize("arch, engine", [("zamba2-1.2b", None),
                                          ("xlstm-1.3b", "fused")])
def test_step_builds_and_runs(arch, engine):
    cell = _cell(arch, engine)
    _, cfg = harness.program_config(cell)
    assert getattr(cfg, "engine", None) == engine
    assert cfg.param_dtype == "float32" and cfg.compute_dtype == "float32"
    fn, opt = harness.program_step(cell)
    key = jax.random.PRNGKey(3)
    params = jax.jit(lambda k: harness.init_weights(cell, k))(key)
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype("float32")}
    toks = jax.random.randint(key, (2, 17), 0, cfg.vocab, jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    new, _, loss = jax.jit(fn)(params, opt.init(params), batch, jnp.int32(0),
                               jax.random.fold_in(key, 1))
    assert np.isfinite(float(loss))
    assert any(not np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(new)))


def test_engine_the_arch_lacks_is_refused():
    cell = _cell("zamba2-1.2b", engine="fused")
    with pytest.raises(ValueError, match=r"zamba2-1\.2b\.smoke.*'engine'"):
        harness.program_config(cell)
