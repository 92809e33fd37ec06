"""The benchmark's reading of layers against the program's own rule.

``bench/trace.py`` keeps its own copy of the rule that names an op's
layer from its op_name, so that the program cannot move the yardstick.
Here it is held to ``repro.launch.trace_names.layer`` on the op_names of
each cell's step, compiled on the CPU at a small size: for every op that
the program's rule scopes, the benchmark's gives the same layer. Each
scope a per-layer metric reads is one of the program's and is found in
the cells that report the metric, and the rules for unscoped ops
leave no instruction of the step's entry without a layer.
"""
import importlib
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, run, trace, traffic  # noqa: E402
from repro.launch import trace_names  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SMALL = {"zaremba-medium": {"vocab": 64, "embed": 130, "hidden": 130},
         "luong-nmt-iwslt15": {"src_vocab": 50, "tgt_vocab": 60,
                               "embed": 128, "hidden": 128}}
TRAFFIC = {"lm": {"batch": 4, "seq": 8, "pool": 4},
           "pairs": {"batch": 4, "max_len": 8, "pairs": 24,
                     "bucket_width": 4,
                     "src_len": {"median": 4, "sigma": 0.5}}}
PROGRAM = set(trace_names.SCOPES) | set(trace_names.RECURRENCE)


@pytest.fixture(scope="module")
def hlo():
    """Each cell's compiled step as text, compiled once."""
    out = {}
    for name in CELLS:
        spec = json.loads((ROOT / "bench" / "workloads" / f"{name}.json")
                          .read_text())
        cell = harness.load_cell(name, sizes=SMALL[spec["config"]],
                                 traffic_override=TRAFFIC[
                                     spec["traffic"]["kind"]])
        batch = jax.tree.map(jnp.asarray, traffic.make_pool(
            cell.spec["traffic"], cell.sizes, 5)[0])
        fn, opt = harness.program_step(cell)
        p = jax.eval_shape(lambda: harness.init_weights(
            cell, jax.random.PRNGKey(0)))
        out[name] = jax.jit(fn).lower(
            p, jax.eval_shape(opt.init, p), batch, jnp.int32(0),
            jax.random.PRNGKey(0)).compile().as_text()
    return out


def _op_names(text):
    return set(re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text))


@pytest.mark.parametrize("cell", CELLS)
def test_rule_agrees_with_program(hlo, cell):
    scoped = 0
    for op in _op_names(hlo[cell]):
        want = trace_names.layer(op)
        if want is None:
            continue
        scoped += 1
        got = trace.scope_of(op, PROGRAM)
        if want == "recurrence":
            assert got in trace_names.RECURRENCE, op
        else:
            assert got == want, op
    assert scoped > 100


def test_metric_scopes_are_the_programs():
    assert run._scopes(MANIFEST) <= PROGRAM


@pytest.mark.parametrize("cell", CELLS)
def test_each_scope_metric_finds_its_scope(hlo, cell):
    found = {trace.scope_of(op, PROGRAM) for op in _op_names(hlo[cell])}
    for m in MANIFEST["per_layer"]:
        scopes = getattr(importlib.import_module(
            f"bench.metrics.{m['name']}"), "SCOPES", ())
        if scopes and cell in m.get("workloads", CELLS):
            assert found & set(scopes), (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_consumer_rule_covers_the_entry(hlo, cell):
    text = hlo[cell]
    mod = trace.Hlo(text, run._scopes(MANIFEST))
    entry = text[text.index("\nENTRY"):]
    names = re.findall(r"^\s+%?([\w.\-]+) = ", entry, re.M)   # not ROOT
    assert names and all(mod.layer(n) for n in names), [
        n for n in names if not mod.layer(n)]
