"""The training step's layers keep their profiler names.

Each benchmark cell's step, compiled at a small size on the CPU as the
benchmark builds it (``bench.harness.program_step``), must give every
matmul a layer: a scope of ``launch/trace_names.py`` or the nested jit
of a scan, directly under the jitted step. A scope that encloses a
scan's nested jit would rename the scan's ops (and the Pallas kernels
the benchmark matches by name), so no op of a scan may sit under one;
the optimizer's scopes lie outside differentiation.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from bench import harness, traffic
from repro.launch import trace_names as tn

CELLS = ["zaremba-medium.ptb-case3", "zaremba-medium.ptb-case1",
         "luong-nmt-iwslt15.envi-case1"]
SMALL = {"zaremba-medium": {"vocab": 64, "embed": 130, "hidden": 130},
         "luong-nmt-iwslt15": {"src_vocab": 50, "tgt_vocab": 60,
                               "embed": 128, "hidden": 128}}
TRAFFIC = {"lm": {"batch": 4, "seq": 8, "pool": 4},
           "pairs": {"batch": 4, "max_len": 8, "pairs": 24,
                     "bucket_width": 4,
                     "src_len": {"median": 4, "sigma": 0.5}}}
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _ops(text):
    """{instruction: (its text, op_name or None)} of compiled HLO."""
    out = {}
    for line in text.splitlines():
        m = INSTR.match(line)
        if m:
            on = OP_NAME.search(m.group(2))
            out[m.group(1)] = (m.group(2), on and on.group(1))
    return out


def _op_names_of(name, ops):
    """The op_name of an instruction, or, where the compiler made it
    without one (the CPU's dot rewrites do), those of its consumers."""
    seen, todo, found = {name}, [name], []
    while todo:
        n = todo.pop()
        if ops[n][1] is not None:
            found.append(ops[n][1])
            continue
        use = re.compile(rf"%{re.escape(n)}(?![\w.])")
        for m, (body, _) in ops.items():
            if m not in seen and use.search(body):
                seen.add(m)
                todo.append(m)
    return found


@pytest.fixture(scope="module", params=CELLS)
def step_ops(request):
    name = request.param
    spec = harness.load_cell(name).spec
    cell = harness.load_cell(
        name, sizes=SMALL[spec["config"]],
        traffic_override=TRAFFIC[spec["traffic"]["kind"]])
    fn, opt = harness.program_step(cell)
    params = jax.eval_shape(
        lambda: harness.init_weights(cell, jax.random.PRNGKey(0)))
    batch = traffic.make_pool(cell.spec["traffic"], cell.sizes, 7)[0]
    text = jax.jit(fn).lower(
        params, jax.eval_shape(opt.init, params),
        jax.tree.map(jnp.asarray, batch), jnp.int32(0),
        jax.random.PRNGKey(1)).compile().as_text()
    return _ops(text)


def test_every_dot_has_a_layer(step_ops):
    dots = [n for n, (body, _) in step_ops.items()
            if re.search(r"\bdot\(", body)]
    assert dots
    for d in dots:
        names = _op_names_of(d, step_ops)
        assert names, f"{d}: no op_name on it or its consumers"
        assert all(tn.layer(on) for on in names), (d, names)


def test_no_scope_above_a_scan(step_ops):
    names = {on for _, on in step_ops.values() if on}
    scans = [on for on in names if any(r in on for r in tn.RECURRENCE)]
    assert scans
    assert all(tn.layer(on) == "recurrence" for on in scans), [
        on for on in scans if tn.layer(on) != "recurrence"][:3]


def test_optimizer_outside_differentiation(step_ops):
    names = {on for _, on in step_ops.values() if on}
    opt = [on for on in names if tn.layer(on) in (tn.GRAD_CLIP, tn.ADAMW)]
    assert {tn.layer(on) for on in opt} == {tn.GRAD_CLIP, tn.ADAMW}
    assert all(on.split("/")[1] in (tn.GRAD_CLIP, tn.ADAMW) for on in opt)


def test_every_model_scope_is_named(step_ops):
    layers = {tn.layer(on) for _, on in step_ops.values() if on}
    assert {tn.EMBED, tn.PHASE_A, tn.OUTPUT, tn.LOSS, tn.GRAD_CLIP,
            tn.ADAMW, "recurrence"} <= layers


@pytest.mark.parametrize("op_name, layer", [
    ("jit(train_step)/jvp(embed)/gather", tn.EMBED),
    ("jit(train_step)/transpose(jvp(loss))/reduce_sum", tn.LOSS),
    ("jit(train_step)/adamw/add", tn.ADAMW),
    ("jit(train_step)/jvp(jit(cell_scan))/pallas_call", "recurrence"),
    ("jit(train_step)/transpose(jvp(jit(_decoder_scan)))/while", "recurrence"),
    ("jit(train_step)/shard_map/grad_psum/psum", tn.GRAD_PSUM),
    ("jit(train_step)/shard_map/jvp(phase_a)/dot_general", tn.PHASE_A),
    ("jit(train_step)/jvp(recurrence)/jit(cell_scan)/pallas_call", None),
    ("jit(train_step)/jvp()/jit(_threefry_fold_in)/add", None),
    ("jit(train_step)/jvp(output)/jvp(loss)/exp", tn.OUTPUT),
    ("params['embed']", None),
])
def test_layer_reads_the_first_name_under_the_step(op_name, layer):
    assert tn.layer(op_name) == layer


def _toy_step_op_names(scoped: bool):
    """op_names of a differentiated nested jit, with a scope around its
    call or without."""
    @jax.jit
    def cell_scan(x):
        return jnp.tanh(x).sum()

    def loss(x):
        if scoped:
            with jax.named_scope("recurrence"):
                return cell_scan(x)
        return cell_scan(x)

    def train_step(x):
        return jax.grad(loss)(x)

    text = jax.jit(train_step).lower(jnp.ones(4)).compile().as_text()
    return {on for _, on in _ops(text).values() if on}


def test_scope_around_a_nested_jit_renames_it():
    """Why the rule exists: a scope around the call moves the nested
    jit below it, and ``layer`` then no longer finds the scan."""
    plain = _toy_step_op_names(scoped=False)
    assert any(tn.layer(on) == "recurrence" for on in plain)
    scoped = _toy_step_op_names(scoped=True)
    assert any("jit(cell_scan)" in on for on in scoped)
    assert not any(tn.layer(on) == "recurrence" for on in scoped)


def test_pallas_site_takes_no_scope():
    def f(x, impl):
        with tn.scope(tn.PHASE_A, impl):
            return x * 2

    for impl, want in (("xla", True), ("pallas", False)):
        text = jax.jit(lambda x: f(x, impl)).lower(
            jnp.ones(3)).compile().as_text()
        assert ("jit(<lambda>)/phase_a/" in text) is want
