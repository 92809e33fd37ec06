"""The one generator of training batches: reads a cell's ``traffic``
parameters and makes a pool of host batches from the run's seed.

``traffic["kind"]`` names a module ``kinds/<kind>.py`` with
``make(traffic, sizes, seed) -> [batch, ...]``; a new kind is a new file.
Kinds today: ``lm`` (fixed-length rows of a language model) and
``pairs`` (translation pairs batched by length); each documents its
parameters.

The seed may be any non-negative integer. All rows of a pool differ, and
every seed gets the same sizes: the seed deals them and draws the ids.
"""
from __future__ import annotations

import importlib

import numpy as np


def zipf_sampler(vocab: int, s: float, lo: int):
    """Draw ids in [lo, vocab) with probability proportional to
    1 / rank**s."""
    p = 1.0 / np.arange(1, vocab - lo + 1, dtype=np.float64) ** s
    cdf = np.cumsum(p / p.sum())

    def draw(rng, shape):
        u = rng.random(shape)
        return (lo + np.minimum(np.searchsorted(cdf, u), vocab - lo - 1)
                ).astype(np.int32)

    return draw


def make_pool(traffic: dict, sizes: dict, seed: int):
    """A list of host batches (dicts of arrays), cycled by the run."""
    kind = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    return kind.make(traffic, sizes, seed)


def shape_of(batch: dict) -> tuple:
    """The shapes of a batch's arrays: one compiled step per shape."""
    return tuple((k, v.shape) for k, v in sorted(batch.items()))
