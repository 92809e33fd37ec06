"""The batch generator: made from the seed alone, rows that differ, the
same multiset of pair lengths for every seed, and pairs batched by
length."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402

W = ROOT / "bench" / "workloads"
LM = json.loads((W / "zaremba-medium.ptb-case3.json").read_text())["traffic"]
PAIRS = json.loads((W / "luong-nmt-iwslt15.envi-case1.json").read_text()
                   )["traffic"]
LM_SIZES = {"vocab": 10000}
PAIR_SIZES = {"src_vocab": 17191, "tgt_vocab": 7709}
SEEDS = [0, 2**31 + 11, 2**40 + 3]


def _pool(kind, seed):
    if kind == "lm":
        return traffic.make_pool(LM, LM_SIZES, seed)
    return traffic.make_pool(PAIRS, PAIR_SIZES, seed)


@pytest.mark.parametrize("kind", ["lm", "pairs"])
def test_same_seed_same_pool(kind):
    a, b = _pool(kind, 7), _pool(kind, 7)
    for x, y in zip(a, b):
        assert x.keys() == y.keys()
        assert all(np.array_equal(x[k], y[k]) for k in x)


@pytest.mark.parametrize("kind", ["lm", "pairs"])
@pytest.mark.parametrize("seed", SEEDS)
def test_rows_all_differ(kind, seed):
    pool = _pool(kind, seed)
    key = "tokens" if kind == "lm" else "src"
    rows = {r.tobytes() for b in pool for r in b[key]}
    assert len(rows) == sum(len(b[key]) for b in pool)


def test_lm_labels_are_next_tokens():
    b = _pool("lm", 3)[0]
    assert b["tokens"].shape == (LM["batch"], LM["seq"])
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 10000


def test_pairs_same_lengths_every_seed():
    def lengths(seed):
        pool = _pool("pairs", seed)
        return sorted((int(b["src_mask"][i].sum()), int(b["tgt_mask"][i].sum()))
                      for b in pool for i in range(PAIRS["batch"]))
    assert lengths(SEEDS[0]) == lengths(SEEDS[1]) == lengths(SEEDS[2])


def test_pairs_layout():
    for b in _pool("pairs", 5):
        P = b["src"].shape[1]
        sl, tl = b["src_mask"].sum(1), (b["tgt_mask"] > 0).sum(1)
        assert sl.min() >= 1 and max(sl.max(), tl.max()) <= P
        assert (b["tgt_in"][:, 0] == 1).all()
        pos = np.arange(P)[None]
        real = pos < tl[:, None]
        assert ((b["tgt_out"] >= 2) == real).all()
        assert np.array_equal(b["tgt_in"][:, 1:][real[:, 1:]],
                              b["tgt_out"][:, :-1][real[:, 1:]])
        assert ((b["src"] >= 2) == (pos < sl[:, None])).all()


def test_pairs_batched_by_length():
    """Each batch is padded to its bucket's edge, a multiple of the
    bucket width, and each of its pairs' longer side lies in that
    bucket. The pool opens with one batch of each bucket, longest
    first."""
    w, M = PAIRS["bucket_width"], PAIRS["max_len"]
    pool = _pool("pairs", 2**31 + 5)
    pads = [b["src"].shape[1] for b in pool]
    for b, P in zip(pool, pads):
        assert P % w == 0 and P <= M and b["tgt_in"].shape[1] == P
        longer = np.maximum(b["src_mask"].sum(1),
                            (b["tgt_mask"] > 0).sum(1))
        assert (longer > P - w).all() and (longer <= P).all()
    edges = sorted(set(pads), reverse=True)
    assert len(edges) > 1 and pads[:len(edges)] == edges


def test_pairs_pool_same_shapes_every_seed():
    def shapes(seed):
        return sorted(traffic.shape_of(b) for b in _pool("pairs", seed))
    assert shapes(SEEDS[0]) == shapes(SEEDS[1]) == shapes(SEEDS[2])
