"""Serving driver: prefill a prompt batch, decode N tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-1.3b --smoke \
        --batch 4 --prompt-len 16 --gen 32

``--loop python`` swaps the on-device chunked decode loop for the
per-token host loop (the pre-PR6 baseline) — useful for A/B'ing the
dispatch overhead. ``--trace N`` serves N synthetic ragged requests
through the continuous-batching scheduler instead of one rectangular
batch and reports sustained tokens/sec.
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.distributed import sharding as shd
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.serving import DecodeEngine, Request, prompt_prefill, serve


def _ragged_trace(n: int, vocab: int, prompt_max: int, gen_max: int,
                  seed: int):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(3, vocab,
                                        int(rng.integers(2, prompt_max + 1))),
                    max_new=int(rng.integers(max(2, gen_max // 4),
                                             gen_max + 1)))
            for i in range(n)]


def main(argv=None):
    compile_cache.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--eos", type=int, default=-1)
    ap.add_argument("--loop", choices=("device", "python"), default="device")
    ap.add_argument("--trace", type=int, default=0,
                    help="serve N ragged requests through the "
                         "continuous-batching scheduler instead of one "
                         "rectangular batch")
    args = ap.parse_args(argv)

    spec = configs.get_arch(args.arch)
    cfg = spec.smoke() if args.smoke else spec.full()
    mesh = mesh_mod.make_host_mesh()
    rules = shd.rules_for_mesh(mesh)
    max_seq = args.max_seq or (args.prompt_len + args.gen)

    init_fn, _, _, _ = steps_mod.param_setup(spec, cfg, mesh, rules,
                                             seed=args.seed)
    params = init_fn()
    vocab = getattr(cfg, "vocab", 256)
    rng = np.random.default_rng(args.seed)

    engine = DecodeEngine(spec=spec, cfg=cfg, params=params,
                          max_seq=max_seq, batch=args.batch, rules=rules,
                          mesh=mesh, temperature=args.temperature,
                          eos_id=args.eos, chunk=args.chunk)

    if args.trace:
        reqs = _ragged_trace(args.trace, vocab, args.prompt_len, args.gen,
                             args.seed)
        t0 = time.time()
        outs = serve(engine, reqs, chunk=args.chunk)
        dt = time.time() - t0
        total = sum(len(v) for v in outs.values())
        print(f"continuous trace: {args.trace} requests over {args.batch} "
              f"slots -> {total} tokens in {dt*1e3:.0f} ms "
              f"({total/max(dt, 1e-9):.1f} tok/s, "
              f"{engine.chunks_run} device dispatches)")
        return 0

    # --- rectangular prefill (both cache kinds go through the shared
    # serving/prefill helper; whisper-style enc-dec keeps its frame branch)
    prompt = rng.integers(3, vocab, size=(args.batch, args.prompt_len))
    prompt = jnp.asarray(prompt, jnp.int32)
    t0 = time.time()
    if spec.kind == "transformer" and (getattr(cfg, "embeds_in", False)
                                       or getattr(cfg, "is_encoder_decoder",
                                                  False)):
        # synthetic-input transformers (embeds-in / whisper enc-dec) build
        # their own prefill batch; adapters.prefill_fn runs the encoder
        batch = {"tokens": prompt[:, :-1]}
        if getattr(cfg, "embeds_in", False):
            batch = {"embeds": jnp.asarray(rng.standard_normal(
                (args.batch, args.prompt_len - 1, cfg.d_model)),
                cfg.compute_dtype)}
        if getattr(cfg, "is_encoder_decoder", False):
            batch["frames"] = jnp.asarray(rng.standard_normal(
                (args.batch, cfg.enc_seq, cfg.d_model)) * 0.02,
                cfg.compute_dtype)
        engine.prefill(batch)
        if getattr(cfg, "embeds_in", False):
            print("prefill ok; embeds-in archs decode from embeddings, not "
                  "token ids — no token decode loop to run")
            return 0
        tok0, pos0 = prompt[:, -1:], args.prompt_len - 1
    else:
        engine.state, tok0, pos0 = prompt_prefill(
            spec, cfg, params, prompt, state=engine.state, rules=rules)
    t_prefill = time.time() - t0

    # --- decode (positions continue after the prefilled prompt)
    t0 = time.time()
    gen = (engine.generate if args.loop == "device"
           else engine.generate_python)
    out = gen(tok0, args.gen, seed=args.seed, start_pos=pos0)
    t_decode = time.time() - t0
    print(f"prefill {args.prompt_len} tok: {t_prefill*1e3:.0f} ms; "
          f"decode {args.gen} tok [{args.loop} loop]: {t_decode*1e3:.0f} ms "
          f"({args.gen*args.batch/max(t_decode,1e-9):.1f} tok/s)")
    print("sample continuation ids:", out[0, :16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
