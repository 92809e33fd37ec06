"""``pairs``: translation pairs, batched by length.

    {"kind": "pairs", "batch": B, "pairs": n, "max_len": M,
     "bucket_width": w, "src_len": {"median": m, "sigma": s},
     "tgt_over_src": r, "zipf": z, "lengths_seed": k}

Lengths: ``n`` pairs drawn from ``lengths_seed`` alone, so every run
trains on the same multiset. A source length is log-normal around ``m``
(``sigma`` s, at least 1); its target ``round(r * source)``. Pairs longer
than ``M`` on either side are left out, as the source's preprocessing
filters them, and drawn again.

Batches: a pair goes to the bucket of the smallest multiple of ``w`` that
holds its longer side, and a batch holds B pairs of one bucket, padded to
that multiple: one compiled shape per bucket. A bucket keeps whole
batches of its pairs in ``lengths_seed``'s order and leaves out the rest.
The run's seed deals each bucket's pairs to its batches, orders the
batches and draws the token ids (Zipf(z) ranks; ids 0 and 1 are padding
and begin-of-sentence). The pool starts with one batch of each bucket,
longest first, so that set-up meets every shape; the rest follow in the
seed's order.

A batch: ``src``, ``tgt_in`` (BOS, then the target), ``tgt_out`` (the
target), all padded with 0, and the padding masks ``src_mask`` (for
attention) and ``tgt_mask`` (for the loss).
"""
from __future__ import annotations

import numpy as np

from bench.traffic import zipf_sampler


def lengths(t: dict) -> np.ndarray:
    """(n, 2) source and target lengths, the same every run."""
    rng = np.random.default_rng(t["lengths_seed"])
    L, M, n = t["src_len"], t["max_len"], t["pairs"]
    out = np.zeros((0, 2), np.int64)
    while len(out) < n:
        src = np.maximum(np.rint(L["median"] * np.exp(
            L["sigma"] * rng.standard_normal(n))), 1)
        tgt = np.maximum(np.rint(t["tgt_over_src"] * src), 1)
        pair = np.stack([src, tgt], -1).astype(np.int64)
        out = np.concatenate([out, pair[pair.max(1) <= M]])
    return out[:n]


def buckets(t: dict) -> dict:
    """{padded length: (m, 2) lengths}, m a multiple of the batch."""
    lens, w, B = lengths(t), t["bucket_width"], t["batch"]
    pad = -(-lens.max(1) // w) * w
    out = {}
    for p in np.unique(pad):
        rows = lens[pad == p]
        if len(rows) >= B:
            out[int(p)] = rows[: len(rows) // B * B]
    return out


def make(t: dict, sizes: dict, seed: int):
    rng = np.random.default_rng(seed)
    B = t["batch"]
    shapes = []                       # (padded length, (B, 2) lengths)
    for p, rows in sorted(buckets(t).items(), reverse=True):
        rows = rows[rng.permutation(len(rows))].reshape(-1, B, 2)
        shapes += [(p, r) for r in rows]
    firsts = [i for i, (p, _) in enumerate(shapes)
              if i == 0 or shapes[i - 1][0] != p]
    rest = [i for i in range(len(shapes)) if i not in firsts]
    order = firsts + [rest[i] for i in rng.permutation(len(rest))]
    d_src = zipf_sampler(sizes["src_vocab"], t["zipf"], 2)
    d_tgt = zipf_sampler(sizes["tgt_vocab"], t["zipf"], 2)
    out = []
    for i in order:
        P, lens = shapes[i]
        pos = np.arange(P)[None, :]
        src_live = pos < lens[:, :1]
        tgt_live = pos < lens[:, 1:]
        src = np.where(src_live, d_src(rng, (B, P)), 0)
        y = np.where(tgt_live, d_tgt(rng, (B, P)), 0)
        tin = np.concatenate([np.ones((B, 1), np.int32), y[:, :-1]], 1)
        out.append({"src": src.astype(np.int32),
                    "tgt_in": np.where(tgt_live, tin, 0).astype(np.int32),
                    "tgt_out": y.astype(np.int32),
                    "src_mask": src_live,
                    "tgt_mask": tgt_live.astype(np.float32)})
    return out
