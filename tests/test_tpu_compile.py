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
import math
import os
import re

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


# The benchmark's cells (bench/workloads/), each step compiled once for
# the module by ``cell_step``.
CELLS = ("zaremba-medium.ptb-case1", "zaremba-medium.ptb-case3",
         "luong-nmt-iwslt15.envi-case1")


@pytest.fixture(scope="module")
def cell_step(one_chip):
    """cell name -> (cell, compiled HLO text) of its training step for the
    described v5e, built as ``bench.harness.program_step`` builds it and
    jitted with the harness's donation."""
    from bench import harness, traffic
    from repro.kernels import cell_scan
    from repro.launch import steps

    done = {}

    def compile_step(name):
        if name in done:
            return done[name]
        cell = harness.load_cell(name)
        with pytest.MonkeyPatch.context() as mp:
            # the step is built on a CPU host, where kernels default to
            # interpret and layouts are the CPU's
            mp.setattr(cell_scan, "resolve_interpret", lambda i: False)
            mp.setattr(steps, "layout_device",
                       lambda: next(iter(one_chip.device_set)))
            jax.clear_caches()
            fn, opt = harness.program_step(cell)
            params = jax.eval_shape(
                lambda: harness.init_weights(cell, jax.random.PRNGKey(0)))
            batch = traffic.make_pool(cell.spec["traffic"], cell.sizes, 7)[0]
            args = (params, jax.eval_shape(opt.init, params), batch,
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32))
            args = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                                args)
            text = jax.jit(fn, donate_argnums=(0, 1)).lower(
                *args).compile().as_text()
            jax.clear_caches()  # nothing traced for the TPU stays for the CPU
        done[name] = cell, text
        return done[name]

    return compile_step


def test_case3_step_keeps_the_benchmark_kernel_names(cell_step):
    """The benchmark finds case III's ``lstm_scan`` kernels in the compiled
    step by op_name (``bench.harness.kernels_missing``) and in the trace
    by instruction name (the cell's ``kernels.lstm_scan.trace``
    patterns). A profiler scope around the scan would rename both."""
    from bench import harness

    cell, text = cell_step("zaremba-medium.ptb-case3")
    kernels = cell.spec["kernels"]
    assert harness.kernels_missing(text, kernels) == []
    calls = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    for pattern in kernels["lstm_scan"]["trace"]:
        assert any(re.match(pattern, c) for c in calls), (pattern, calls)


def _entry(text):
    """The entry computation of compiled HLO text: (name -> (opcode,
    operand names, shape), name of its ROOT)."""
    body = text[text.index("\nENTRY "):]
    body = body[body.index("{\n") + 2:body.index("\n}")]
    insts, root = {}, None
    for line in body.splitlines():
        m = re.match(r"\s*(ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$",
                     line)
        if not m:
            continue
        is_root, name, shape, op, rest = m.groups()
        depth = 1
        for i, ch in enumerate(rest):       # the operands end at the ')'
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if not depth:
                break
        if 'custom_call_target="ConcatBitcast"' in rest:
            op = "ConcatBitcast"
        insts[name] = op, re.findall(r"%([\w.\-]+)", rest[:i]), shape
        if is_root:
            root = name
    return insts, root


# ops that move a buffer between memories or re-type it without touching
# its layout: a copy behind them copies the buffer they were given
MOVES = ("bitcast", "copy-start", "copy-done", "slice-start", "slice-done",
         "ConcatBitcast")


def _origin(insts, name):
    while name in insts and insts[name][0] in MOVES:
        name = insts[name][1][0]
    return name


def _elements(shape):
    dims = shape.split("[", 1)[1].split("]", 1)[0]
    return math.prod(int(d) for d in dims.split(",") if d)


@pytest.mark.parametrize("name", CELLS)
def test_step_keeps_tables_in_their_stored_layout(cell_step, name):
    """No parameter or AdamW moment of 1M elements or more is copied, but
    for the one copy of a table into the layout its gather reads; the
    update writes every output in the layout it is stored in, so no copy
    feeds the step's outputs (``steps.pin_grad_layouts``)."""
    insts, root = _entry(cell_step(name)[1])
    big = {n for n, (op, _, shape) in insts.items()
           if op == "parameter" and _elements(shape) >= 1_000_000}
    fed = {}
    for n, (op, operands, _) in insts.items():
        if op == "copy" and _origin(insts, operands[0]) in big:
            fed.setdefault(_origin(insts, operands[0]), []).append(n)
    assert not [p for p in fed if not p.startswith("params_")], fed
    assert all(len(c) == 1 for c in fed.values()), fed
    outs = [_origin(insts, o) for o in insts[root][1]]
    assert not [o for o in outs if insts.get(o, ("",))[0] == "copy"], outs


def test_grad_layout_pins_on_v5e(one_chip, monkeypatch):
    """On a v5e the tall embedding table is stored column-major and its
    gradient is pinned to that layout; the square-ish and wide matrices
    and the biases are stored row-major and left alone."""
    from bench import harness
    from repro.launch import steps

    monkeypatch.setattr(steps, "layout_device",
                        lambda: next(iter(one_chip.device_set)))
    cell = harness.load_cell("zaremba-medium.ptb-case1")
    params = jax.eval_shape(
        lambda: harness.init_weights(cell, jax.random.PRNGKey(0)))
    pins, n, nbytes = steps.grad_layout_pins(params)
    by_name = {jax.tree_util.keystr(p): lay for (p, _), lay in zip(
        jax.tree_util.tree_flatten_with_path(params)[0], pins)}
    assert params["embed"].shape == (10000, 650)
    assert by_name["['embed']"].major_to_minor == (1, 0)    # XLA's {0,1}
    assert by_name["['lstm'][0]['U']"] is None
    assert by_name["['fc']['w']"] is None
    assert [k for k, lay in by_name.items() if lay is not None] == [
        "['embed']"]
    assert (n, nbytes) == (1, 10000 * 650 * 4)
