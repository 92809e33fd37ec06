"""The comparison that decides ``correct``.

The program's readings of its first training steps (``harness.first_steps``)
against the plain reference's readings of the same steps. Three numbers:

``loss_gap``    the largest ``|loss - ref| / |ref|`` over the steps;
``grad_gap``    over the leaves, the largest gap between the norm of the
                program's first (clipped) gradient and the reference's,
                over the reference's norm of that leaf or of the median
                leaf, whichever is larger;
``update_gap``  the same, of each leaf's change over the steps. Leaves
                whose reference gradient is under ``NEGLIGIBLE`` of the
                median leaf's are left out: AdamW moves them by round-off
                alone.

Each is held to its cell's limit (``limits`` in the cell's file); a
number at or under its limit passes. A reading that is not finite fails.
"""
from __future__ import annotations

import math
import statistics

NEGLIGIBLE = 1e-3
NAMES = ("loss_gap", "grad_gap", "update_gap")


def _worst(gaps: dict) -> tuple:
    """(largest gap, its key); a NaN gap is the worst of all."""
    for k, g in gaps.items():
        if math.isnan(g):
            return g, k
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def _leaf_gaps(got: dict, want: dict, leaves) -> dict:
    floor = statistics.median(want.values())
    return {k: abs(got[k] - want[k]) / max(want[k], floor) for k in leaves}


def compare(prog: dict, ref: dict) -> dict:
    """{name: (value, worst leaf or step)} of the three numbers."""
    loss = {f"step {i}": abs(a - b) / abs(b)
            for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]))}
    floor = statistics.median(ref["grad"].values())
    moving = [k for k, v in ref["grad"].items() if v >= NEGLIGIBLE * floor]
    return {"loss_gap": _worst(loss),
            "grad_gap": _worst(_leaf_gaps(prog["grad"], ref["grad"],
                                          ref["grad"])),
            "update_gap": _worst(_leaf_gaps(prog["delta"], ref["delta"],
                                            moving))}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) of ``compare``'s numbers; a
    reading that is not finite is given as a string, so the line stays
    JSON."""
    out, ok = {}, True
    for name in NAMES:
        value = numbers[name][0]
        finite = math.isfinite(value)
        out[name] = {"value": value if finite else str(value),
                     "limit": limits[name]}
        ok &= finite and value <= limits[name]
    return ok, out
