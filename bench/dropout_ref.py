"""The dropout masks a training step draws, sampled without the program.

The program samples its masks from ``drop_key`` and the step inside the
jitted step, so a reference that is to follow it needs the same masks.
This module re-derives them from the documented contract of the plan
(``repro.core.dropout_plan``), with plain ``jax.random`` calls:

* the step is folded into the key once; each site's stream is the key
  folded with the CRC-32 of the site's full name (masked to 31 bits);
* a per-step site folds the time index ``t`` into its site key; a site
  applied once to a whole ``(B, T, D)`` activation takes no ``t``;
* a site's spec is the plan's entry for the full name, else for its last
  path component, else the site is off;
* structured (case III) masks keep ``nb - ceil(rate * nb)`` of the
  ``nb = D / block`` blocks: the first ones of a random permutation of the
  block ids, the same for every row, scaled by ``D / kept units``; the
  block is the largest divisor of ``D`` not above the one asked for;
* random (case I) masks are Bernoulli(1 - rate) per element of the
  flattened rows, scaled by ``1 / (1 - rate)``.

Each mask is returned dense and already scaled, as an array that
broadcasts against the activation it multiplies.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

CASES = {"case1": "random", "case3": "structured"}     # both per step


def site_stream(site: str) -> int:
    return zlib.crc32(site.encode("utf-8")) & 0x7FFFFFFF


def fit_block(block: int, dim: int) -> int:
    bs = min(block, dim)
    while dim % bs:
        bs -= 1
    return bs


def kept_blocks(dim: int, rate: float, block: int) -> int:
    nb = dim // block
    dropped = min(max(math.ceil(rate * nb), 0), nb - 1) if rate > 0 else 0
    return nb - dropped


def site_spec(plan: dict, site: str):
    """The plan's entry for the full site name, else for its last path
    component, else None (the site is off)."""
    if site in plan:
        return plan[site]
    return plan.get(site.rsplit("/", 1)[-1])


class Masks:
    """Masks of one training step: ``plan`` is the cell's plan as JSON
    (site -> {"case", "rate", "block", ...}), ``key`` the step's
    ``drop_key``."""

    def __init__(self, plan: dict, key, step, dtype=jnp.float32):
        self.plan = plan
        self.key = jax.random.fold_in(key, step)
        self.dtype = dtype

    def _site_key(self, site: str):
        return jax.random.fold_in(self.key, site_stream(site))

    def _one(self, spec, k, rows: int, dim: int):
        rate = float(spec["rate"])
        if CASES[spec["case"]] == "structured":
            bs = fit_block(int(spec.get("block", 1)), dim)
            nb, nk = dim // bs, kept_blocks(dim, rate, bs)
            kb = jnp.sort(jax.random.permutation(k, nb)[:nk])
            blk = jnp.zeros((nb,), jnp.float32).at[kb].set(1.0)
            scale = dim / (nk * bs)
            return jnp.repeat(blk, bs) * scale                  # (dim,)
        keep = jax.random.bernoulli(k, 1.0 - rate, (rows, dim))
        return keep.astype(jnp.float32) / (1.0 - rate)          # (rows, dim)

    def whole(self, site: str, lead: tuple, dim: int):
        """Mask for one application to a ``(*lead, dim)`` activation, or
        None when the site is off."""
        spec = site_spec(self.plan, site)
        if spec is None or float(spec["rate"]) <= 0:
            return None
        m = self._one(spec, self._site_key(site), math.prod(lead), dim)
        if m.ndim == 2:
            m = m.reshape(*lead, dim)
        return m.astype(self.dtype)

    def per_step(self, site: str, steps: int, batch: int, dim: int):
        """``(steps, batch|1, dim)`` masks of a per-step site, or None."""
        spec = site_spec(self.plan, site)
        if spec is None or float(spec["rate"]) <= 0:
            return None
        base = self._site_key(site)
        keys = jax.vmap(lambda t: jax.random.fold_in(base, t))(
            jnp.arange(steps))
        ms = jax.vmap(lambda k: self._one(spec, k, batch, dim))(keys)
        if ms.ndim == 2:                                    # structured
            ms = ms[:, None, :]
        return ms.astype(self.dtype)
