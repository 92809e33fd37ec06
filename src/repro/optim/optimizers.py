"""Optimizers as (init, update) pairs over param pytrees.

``update(grads, state, params) -> (updates, state)`` followed by
``apply_updates``; mirrors the optax contract so swapping in optax later is
mechanical. Moments are kept in fp32 regardless of param dtype (bf16 params
+ fp32 m/v is the deployment configuration costed in EXPERIMENTS §Dry-run).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.launch import trace_names as tn


class Optimizer(NamedTuple):
    init: Callable
    update: Callable


OptState = Any


def apply_updates(params, updates):
    with jax.named_scope(tn.ADAMW):
        return jax.tree.map(
            lambda p, u: (p + u.astype(p.dtype)).astype(p.dtype), params,
            updates)


def global_norm(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(max_norm: float) -> Optimizer:
    def init(params):
        return ()

    def update(grads, state, params=None):
        with jax.named_scope(tn.GRAD_CLIP):
            g = global_norm(grads)
            scale = jnp.minimum(1.0, max_norm / jnp.maximum(g, 1e-12))
            return jax.tree.map(lambda x: x * scale, grads), state

    return Optimizer(init, update)


def sgd(lr) -> Optimizer:
    """lr: float or callable(step) -> float. State = step counter."""
    def init(params):
        return jnp.zeros((), jnp.int32)

    def update(grads, step, params=None):
        rate = lr(step) if callable(lr) else lr
        return jax.tree.map(lambda g: -rate * g.astype(jnp.float32), grads), \
            step + 1

    return Optimizer(init, update)


def adamw(lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0) -> Optimizer:
    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"m": jax.tree.map(zeros, params),
                "v": jax.tree.map(zeros, params),
                "step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        with jax.named_scope(tn.ADAMW):
            step = state["step"] + 1
            rate = lr(step) if callable(lr) else lr
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                             state["m"], grads)
            v = jax.tree.map(lambda v, g: b2 * v
                             + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                             state["v"], grads)
            mh = jax.tree.map(lambda m: m / (1 - b1 ** step), m)
            vh = jax.tree.map(lambda v: v / (1 - b2 ** step), v)
            upd = jax.tree.map(
                lambda mh, vh, p: -rate * (mh / (jnp.sqrt(vh) + eps)
                                           + weight_decay * p.astype(jnp.float32)),
                mh, vh, params)
            return upd, {"m": m, "v": v, "step": step}

    return Optimizer(init, update)


def nt_asgd(lr, trigger_window: int = 5) -> Optimizer:
    """Non-monotonically-triggered ASGD (AWD-LSTM's optimizer).

    SGD until validation stops improving (caller flips ``state["avg_on"]``
    via ``trigger_averaging``), then iterate averaging of parameters.
    The averaged copy lives in the state; ``averaged_params`` reads it out.
    """
    def init(params):
        return {"step": jnp.zeros((), jnp.int32),
                "avg_on": jnp.zeros((), jnp.bool_),
                "avg_start": jnp.zeros((), jnp.int32),
                "avg": jax.tree.map(lambda p: p.astype(jnp.float32), params)}

    def update(grads, state, params):
        step = state["step"] + 1
        rate = lr(step) if callable(lr) else lr
        upd = jax.tree.map(lambda g: -rate * g.astype(jnp.float32), grads)
        # running average of the *post-update* params when triggered
        k = jnp.maximum(step - state["avg_start"], 1).astype(jnp.float32)
        new_avg = jax.tree.map(
            lambda a, p, u: jnp.where(
                state["avg_on"],
                a + ((p.astype(jnp.float32) + u) - a) / k,
                p.astype(jnp.float32) + u),
            state["avg"], params, upd)
        return upd, {**state, "step": step, "avg": new_avg}

    return Optimizer(init, update)


def trigger_averaging(state):
    return {**state, "avg_on": jnp.ones((), jnp.bool_),
            "avg_start": state["step"]}


def averaged_params(state, params):
    return jax.tree.map(lambda a, p: a.astype(p.dtype), state["avg"], params)


def chain(*opts: Optimizer) -> Optimizer:
    """Compose transforms left-to-right (e.g. clip -> adamw)."""
    def init(params):
        return tuple(o.init(params) for o in opts)

    def update(grads, states, params):
        new_states = []
        for o, s in zip(opts, states):
            grads, s = o.update(grads, s, params)
            new_states.append(s)
        return grads, tuple(new_states)

    return Optimizer(init, update)
