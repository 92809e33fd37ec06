"""``lm``: ``{"kind": "lm", "batch": B, "seq": T, "zipf": s, "pool": n}``.

``n`` batches of B rows of T + 1 token ids, drawn with Zipf(s) rank
frequencies over the vocabulary; ``tokens`` are the first T and
``labels`` the last T. Every position bears loss.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import zipf_sampler


def make(t: dict, sizes: dict, seed: int):
    rng = np.random.default_rng(seed)
    draw = zipf_sampler(sizes["vocab"], t["zipf"], 0)
    out = []
    for _ in range(t["pool"]):
        rows = draw(rng, (t["batch"], t["seq"] + 1))
        out.append({"tokens": rows[:, :-1], "labels": rows[:, 1:]})
    return out
