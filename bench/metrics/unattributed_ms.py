"""Device time per step that no layer takes: ops with no scope that a
metric reads and no first consumer with one, and ops of other modules.
0 where every op found a layer."""
from bench import trace


def read(r):
    s = r.trace["layer_s"].get(trace.UNATTRIBUTED, 0.0)
    return 1e3 * s / r.steps if r.steps else None
