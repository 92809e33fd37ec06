"""Device busy time per step (union of its operations' intervals)."""


def read(r):
    return 1e3 * r.trace["busy_s"] / r.steps if r.steps else None
