"""The check that decides ``correct``, at a small size on the CPU.

Each cell's run, without the harness's look for a TPU, with the timed
path as the benchmark runs it and with it broken underneath: the
control (the plain reference in bfloat16 put in the program's place), a
step that returns its state unchanged, and a step fed half of each
batch (its loss the mean over the rest). The program has to come out
correct and each of the others not, under the cell's own limits.
"""
import argparse
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, run  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SMALL = {"zaremba-medium": {"vocab": 64, "embed": 130, "hidden": 130},
         "luong-nmt-iwslt15": {"src_vocab": 50, "tgt_vocab": 60,
                               "embed": 128, "hidden": 128}}
TRAFFIC = {"lm": {"batch": 4, "seq": 8, "pool": 4},
           "pairs": {"batch": 4, "max_len": 8, "pairs": 24,
                     "bucket_width": 4,
                     "src_len": {"median": 4, "sigma": 0.5}}}


def _run(cell, variant):
    spec = json.loads((ROOT / "bench" / "workloads" / f"{cell}.json")
                      .read_text())
    args = argparse.Namespace(workload=cell, seed=2**31 + 29, seconds=0.2,
                              trace=0)
    return run.run(args, require_tpu=False, variant=variant,
                   sizes=SMALL[spec["config"]],
                   traffic_override=TRAFFIC[spec["traffic"]["kind"]])


@pytest.mark.parametrize("variant, correct", [
    ("program", True), ("control", False), ("frozen", False),
    ("half_batch", False)])
@pytest.mark.parametrize("cell", CELLS)
def test_check_catches(cell, variant, correct):
    res = _run(cell, variant)
    assert res["correct"] is correct
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(check.NAMES) | {"kernels_missing"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "step_ms_p95", "setup_s"}


def test_nan_reading_fails():
    nan = float("nan")
    ref = {"losses": [1.0, 1.0], "grad": {"a": 1.0, "b": 2.0},
           "delta": {"a": 1.0, "b": 1.0}}
    got = {"losses": [nan, 1.0], "grad": {"a": 1.0, "b": nan},
           "delta": {"a": 1.0, "b": 1.0}}
    nums = check.compare(got, ref)
    assert nums["loss_gap"][1] == "step 0" and nums["grad_gap"][1] == "b"
    ok, _ = check.verdict(nums, {"loss_gap": 1, "grad_gap": 1,
                                 "update_gap": 1})
    assert not ok


def test_negligible_leaves_left_out_of_update_gap():
    """A leaf whose reference gradient is under a thousandth of the median
    leaf's moves by round-off under AdamW: its change is not compared."""
    ref = {"losses": [1.0], "grad": {"a": 1.0, "b": 1.0, "c": 1e-5},
           "delta": {"a": 1.0, "b": 1.0, "c": 1.0}}
    got = {"losses": [1.0], "grad": {"a": 1.0, "b": 1.0, "c": 1e-5},
           "delta": {"a": 1.0, "b": 1.0, "c": 3.0}}
    assert check.compare(got, ref)["update_gap"][0] == 0.0
    got["delta"]["a"] = 1.5
    assert check.compare(got, ref)["update_gap"] == (0.5, "a")
