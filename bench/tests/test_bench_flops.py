"""The required-FLOPs functions of ``bench/configs`` against the dot
FLOPs that XLA compiles, counted loop-aware by ``launch/hlo_cost.py``.

At a small size on the CPU, with every row at full length (so no
padding), case I's xla step computes every matmul dense, and the count
has to agree with the required FLOPs exactly, up to the dots named
below. Case III counts only the kept blocks' rows; the test names the
sites that the program's xla step still runs dense.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, traffic  # noqa: E402
from repro.launch import hlo_cost  # noqa: E402

SMALL = {"zaremba-medium": {"vocab": 64, "embed": 130, "hidden": 130},
         "luong-nmt-iwslt15": {"src_vocab": 50, "tgt_vocab": 60,
                               "embed": 128, "hidden": 128}}
FULL_ROWS = {"lm": {"batch": 4, "seq": 8, "pool": 1},
             "pairs": {"batch": 4, "max_len": 8, "pairs": 4,
                       "bucket_width": 8, "tgt_over_src": 1.0,
                       "src_len": {"median": 8, "sigma": 0.0}}}
NMT_CASE3 = {site: {"case": "case3", "rate": 0.3, "block": 64,
                    "impl": impl}
             for site, impl in (("nr", "xla"), ("dec/feed/nr", "pallas"),
                                ("rh", "pallas"), ("out", "xla"))}


def _cell(name, xla_everywhere=False):
    spec = json.loads((ROOT / "bench" / "workloads" / f"{name}.json")
                      .read_text())
    cell = harness.load_cell(name, sizes=SMALL[spec["config"]],
                             traffic_override=FULL_ROWS[
                                 spec["traffic"]["kind"]])
    if xla_everywhere:
        cell.spec["plan"] = {k: {**v, "impl": "xla"}
                             for k, v in cell.plan.items()}
    return cell


def _hlo_flops(cell, batch):
    fn, opt = harness.program_step(cell)
    p = jax.eval_shape(lambda: harness.init_weights(cell,
                                                    jax.random.PRNGKey(0)))
    o = jax.eval_shape(opt.init, p)
    compiled = jax.jit(fn).lower(p, o, batch, jnp.int32(0),
                                 jax.random.PRNGKey(0)).compile()
    return hlo_cost.analyze_hlo(compiled.as_text()).flops


def _dense(site, dim, steps=1):
    return dim


def test_lm_case1_equals_hlo():
    cell = _cell("zaremba-medium.ptb-case1")
    batch = traffic.make_pool(cell.spec["traffic"], cell.sizes, 3)[0]
    required = cell.mod.step_flops(cell.sizes, harness.kept_fn(cell.plan),
                                   batch)
    assert required == cell.mod.step_flops(cell.sizes, _dense, batch)
    assert _hlo_flops(cell, batch) == required


def test_nmt_case1_equals_hlo_but_attention_products():
    """decoder_scan's xla backward recomputes each step's context
    (``alpha @ enc``: one dot more than the math needs) and forms the
    gradients of the encoder memory and its projection as outer products,
    which XLA computes elementwise and not as dots (two products fewer).
    Net: one attention product, 2 B T S H FLOPs, short."""
    cell = _cell("luong-nmt-iwslt15.envi-case1")
    batch = traffic.make_pool(cell.spec["traffic"], cell.sizes, 3)[0]
    required = cell.mod.step_flops(cell.sizes, harness.kept_fn(cell.plan),
                                   batch)
    B, T = batch["tgt_in"].shape
    S, H = batch["src"].shape[1], cell.sizes["hidden"]
    assert _hlo_flops(cell, batch) == required - 2 * B * T * S * H


def test_lm_case3_counts_kept_blocks_only():
    """Case III, block 65 of 130 units, rate 0.5: one of two blocks kept
    at every site. The required count takes the kept rows of the
    recurrent, non-recurrent and output matmuls. With every site on xla,
    the program runs the recurrent matmuls compact, but the time-batched
    non-recurrent matmuls as one masked-dense gemm and the output layer
    dense behind a multiplied mask: its dots count those dense."""
    cell = _cell("zaremba-medium.ptb-case3", xla_everywhere=True)
    batch = traffic.make_pool(cell.spec["traffic"], cell.sizes, 3)[0]
    kept = harness.kept_fn(cell.plan)
    assert kept("lstm/layer0/rh", 130) == 65
    required = cell.mod.step_flops(cell.sizes, kept, batch)
    V, H = cell.sizes["vocab"], cell.sizes["hidden"]
    tokens = batch["tokens"].size
    dense = cell.mod.step_flops(cell.sizes, _dense, batch)
    dropped_rows = 2 * 65 * 4 * H * 2 + 2 * 65 * 4 * H * 2 + 2 * 65 * V
    assert required == dense - 3 * tokens * dropped_rows

    def as_run(site, dim):
        return dim if site.endswith("/nr") or site == "out" else kept(
            site, dim)

    assert _hlo_flops(cell, batch) == cell.mod.step_flops(cell.sizes, as_run,
                                                          batch)


@pytest.mark.parametrize("name", ["zaremba-medium.ptb-case3",
                                  "luong-nmt-iwslt15.envi-case1"])
def test_kernel_work_within_step(name):
    """A kernel's required FLOPs are part of the step's. The NMT cell is
    given the case III plan that would run both of its kernels."""
    cell = _cell(name)
    if cell.spec["config"] == "luong-nmt-iwslt15":
        cell.spec["plan"] = NMT_CASE3
    batch = traffic.make_pool(cell.spec["traffic"], cell.sizes, 3)[0]
    kept = harness.kept_fn(cell.plan)
    step = cell.mod.step_flops(cell.sizes, kept, batch)
    work = cell.mod.kernel_work(cell.sizes, kept, batch)
    assert set(cell.spec["kernels"]) <= set(work)
    assert 0 < sum(f for f, _ in work.values()) < step
    assert all(b > 0 for _, b in work.values())


def test_lm_case3_kernel_bytes_count_kept_rows():
    """lstm_scan's bytes at the small size, case III: of U and dU, the
    rows that some step of the T keeps. One of two blocks is kept per
    step, so a row is never kept with chance 2**-T. The other arrays are
    counted whole: gx, the outputs and the states."""
    cell = _cell("zaremba-medium.ptb-case3")
    batch = traffic.make_pool(cell.spec["traffic"], cell.sizes, 3)[0]
    kept = harness.kept_fn(cell.plan)
    H, (B, T) = cell.sizes["hidden"], batch["tokens"].shape
    assert kept("lstm/layer0/rh", H, 1) == H // 2
    union = H * (1 - 0.5 ** T)
    assert kept("lstm/layer0/rh", H, T) == pytest.approx(union)
    gx, ys, st = B * T * 4 * H, B * T * H, 2 * B * H
    want = 2 * 4 * (3 * (gx + union * 4 * H + st) + 2 * (ys + st))
    _, got = cell.mod.kernel_work(cell.sizes, kept, batch)["lstm_scan"]
    assert got == pytest.approx(want)
    _, dense = cell.mod.kernel_work(cell.sizes, _dense, batch)["lstm_scan"]
    assert got < dense
