"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax init; everything else sees
the real device count).

  single-pod: (16, 16)    axes ("data", "model")   = 256 chips (one v5e pod)
  multi-pod:  (2, 16, 16) axes ("pod", "data", "model") = 512 chips; "pod"
              is the DCN axis — gradient sync crosses it once per step,
              optionally int8-compressed (optim.compress).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: sharding is propagated by the
    compiler from the arrays' NamedShardings and the ``shard_act``
    constraints, as the model code assumes (``jax.make_mesh`` defaults to
    Explicit axes, under which every op must type its own sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (CPU tests: 1 device)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def make_data_mesh(n: int):
    """(n, 1) ("data", "model") mesh over the first ``n`` host devices.

    The data-parallel training mesh (launch/train.py --mesh, the
    distributed tests' device sweep): batch shards over "data", params
    replicate over the size-1 "model" axis. Raises if the host has fewer
    than ``n`` devices."""
    devs = jax.devices()
    if n < 1 or n > len(devs):
        raise ValueError(f"mesh size {n} out of range for "
                         f"{len(devs)} host devices")
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:n]).reshape(n, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
