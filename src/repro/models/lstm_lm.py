"""LSTM language models from the paper's Table 1.

Zaremba'14 medium (2x650, NR dropout .5) / large (2x1500, .65) and
AWD-LSTM (3x1150, embed 400, dropout vector [.4,.1,.25,.4] + recurrent .5).
The dropout *pattern* (Case I-IV, NR / NR+RH placement) is the experiment
variable — a ``DropoutPlan`` over the named sites

    "embed"  after the embedding lookup
    "nr"     non-recurrent input of every LSTM layer
    "rh"     recurrent hidden of every LSTM layer (the paper's extension)
    "out"    pre-FC output dropout

so benchmarks flip one knob (``cfg.plan``) while the model stays fixed.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import layers as L
from repro.core import lstm as lstm_mod
from repro.core import metrics
from repro.core.dropout_plan import DropoutPlan
from repro.core.sdrop import DropoutSpec
from repro.launch import trace_names as tn


@dataclasses.dataclass(frozen=True)
class LSTMLMConfig:
    name: str = "lstm_lm"
    vocab: int = 10000
    embed: int = 650
    hidden: int = 650
    num_layers: int = 2
    tie_embeddings: bool = False
    init_scale: float = 0.05
    plan: DropoutPlan = DropoutPlan()
    # recurrent execution engine: "scheduled" (two-phase: masks + NR matmuls
    # hoisted out of the scan), "fused" (Phase B as one persistent-scan
    # kernel per layer) or "stepwise" (in-scan reference)
    engine: str = "scheduled"
    param_dtype: Any = jnp.float32
    loss_chunks: int = 4


def _mk(defaults: dict, kw: dict) -> LSTMLMConfig:
    return LSTMLMConfig(**{**defaults, **kw})


def zaremba_medium(**kw) -> LSTMLMConfig:
    return _mk(dict(name="zaremba_medium", vocab=10000, embed=650, hidden=650,
                    num_layers=2, init_scale=0.05,
                    plan=DropoutPlan.case("case3", 0.5,
                                          sites=("embed", "nr", "out"))), kw)


def zaremba_large(**kw) -> LSTMLMConfig:
    return _mk(dict(name="zaremba_large", vocab=10000, embed=1500, hidden=1500,
                    num_layers=2, init_scale=0.04,
                    plan=DropoutPlan.case("case3", 0.65,
                                          sites=("embed", "nr", "out"))), kw)


def awd_lstm(**kw) -> LSTMLMConfig:
    return _mk(dict(name="awd_lstm", vocab=10000, embed=400, hidden=1150,
                    num_layers=3, tie_embeddings=True,
                    plan=DropoutPlan({"embed": DropoutSpec(rate=0.4),
                                      "nr": DropoutSpec(rate=0.25),
                                      "rh": DropoutSpec(rate=0.5),
                                      "out": DropoutSpec(rate=0.4)})), kw)


def init_params(key, cfg: LSTMLMConfig):
    k_e, k_l, k_f = jax.random.split(key, 3)
    p = {
        "embed": L.uniform_init(k_e, (cfg.vocab, cfg.embed), 0.1,
                                cfg.param_dtype),
        "lstm": lstm_mod.init_lstm_params(
            k_l, cfg.embed, cfg.hidden, cfg.num_layers,
            init_scale=cfg.init_scale, dtype=cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        p["fc"] = L.init_dense(k_f, cfg.hidden, cfg.vocab,
                               scale=cfg.init_scale, dtype=cfg.param_dtype)
    elif cfg.hidden != cfg.embed:
        p["proj"] = L.init_dense(k_f, cfg.hidden, cfg.embed, bias=False,
                                 dtype=cfg.param_dtype)
    return p


def forward(params, tokens, cfg: LSTMLMConfig, *, state=None, ctx=None,
            lengths=None):
    """tokens: (B, S) -> (logits (B,S,V), final state).

    ``lengths`` (B,) int32 marks a ragged batch: row b's recurrent carries
    freeze after its length (so the returned state carries over correctly
    in truncated-BPTT training) and frozen steps cost zero gradient.
    """
    if ctx is None:
        ctx = cfg.plan.bind(None)
    B, S = tokens.shape
    with jax.named_scope(tn.EMBED):
        x = jnp.take(params["embed"], tokens, axis=0)     # (B,S,E)
        x = ctx.apply("embed", x).transpose(1, 0, 2)      # (S,B,E)
    if state is None:
        state = lstm_mod.zero_state(cfg.num_layers, B, cfg.hidden)
    ys, state = lstm_mod.lstm_stack(
        params["lstm"], x, state, ctx=ctx, engine=cfg.engine,
        lengths=lengths)
    with jax.named_scope(tn.OUTPUT):
        h = ys.transpose(1, 0, 2)                          # (B,S,H)
        h = ctx.apply("out", h)
        if cfg.tie_embeddings:
            if "proj" in params:
                h = L.dense(params["proj"], h)
            logits = jnp.einsum("bsh,vh->bsv", h, params["embed"],
                                preferred_element_type=jnp.float32)
        else:
            logits = L.dense(params["fc"], h).astype(jnp.float32)
    return logits, state


def loss_fn(params, batch, cfg: LSTMLMConfig, *, state=None, drop_key=None,
            rules=None, step=0, shard=None):
    """Mean NLL per token — per *real* token when batch carries "lengths".

    ``shard`` (core.dropout_plan.BatchShard) marks this call as one batch
    shard of a data-parallel step: dense dropout masks are sampled at the
    global batch size and row-sliced so sharded grads match single-device.
    """
    ctx = cfg.plan.bind(drop_key, step, shard=shard)
    lengths = batch.get("lengths")
    logits, _ = forward(params, batch["tokens"], cfg, state=state, ctx=ctx,
                        lengths=lengths)
    with jax.named_scope(tn.LOSS):
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(lp, batch["labels"][..., None], axis=-1)
        if lengths is None:
            return nll.mean()
        mask = metrics.length_mask(lengths, batch["tokens"].shape[1])
        return metrics.masked_mean(nll[..., 0], mask)


def perplexity(params, tokens, labels, cfg: LSTMLMConfig,
               lengths=None) -> float:
    """exp(mean NLL) — over real tokens only when ``lengths`` is given."""
    logits, _ = forward(params, tokens, cfg, lengths=lengths)
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)
    if lengths is None:
        return float(jnp.exp(nll.mean()))
    mask = metrics.length_mask(lengths, tokens.shape[1])
    return float(jnp.exp(metrics.masked_mean(nll[..., 0], mask)))
