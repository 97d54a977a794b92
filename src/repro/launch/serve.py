"""Serving driver: batched prefill + decode with a KV/state cache.

CPU preset serves a REDUCED config; the same driver lowers the full config
on a TPU mesh (the decode shapes of the dry-run are exactly this step).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --batch 4 --prompt-len 32 --gen 32

``--ranks N`` switches to the DISTRIBUTED serve tier instead
(``repro.serve``): a router rank admits an open-loop Poisson session
population and N-1 workers run continuous-batching decode over the
rank-sharded dynamic-window page cache — the comm-core data plane the
single-process path above feeds in a real deployment.

  PYTHONPATH=src python -m repro.launch.serve --ranks 3 --sessions 32
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import blocks, lm

# The host spans of a served batch, as ``jax.profiler.TraceAnnotation``
# names: admission, the prompt's steps, each generated token's step, and
# the read of each token to the host. They share a profile's clock with
# the device's ops, so a profile names each idle gap of the device by
# what the host was doing in it.
SPANS = ("batch_admit", "prefill", "decode", "host_read")


def make_decode_fn(cfg):
    """The jitted one-token serving step: (params, state, tok (B, 1),
    pos (B,)) -> (logits (B, vocab), state)."""
    @jax.jit
    def decode_fn(params, state, tok, pos):
        b = {"tokens": tok}
        if cfg.frontend == "frames":
            with blocks.scope("embed"):
                emb = blocks.cast(params["embed"],
                                  jnp.dtype(cfg.compute_dtype))
                b = {"frames": emb[tok[:, 0]][:, None, :]}
        return lm.decode_step(params, cfg, state, b, pos)

    return decode_fn


def serve_batch(cfg, *, batch: int, prompt_len: int, gen: int,
                seed: int = 0, greedy: bool = True, quiet: bool = False,
                params=None, keep_logits: int = 0) -> dict:
    """Prefill a batch of prompts, then decode `gen` tokens each.

    ``params`` defaults to ``lm.init`` from ``seed``. The first call of the
    step (trace, compile and one step) is timed as ``compile_s`` before the
    prefill clock starts; both clocks stop on ``block_until_ready``, so
    ``prefill_s`` and ``decode_s`` time device work, not the enqueue.
    ``keep_logits`` returns the logits each of the first that many
    generated tokens was picked from (as device arrays, under "logits").
    """
    if params is None:
        params = jax.jit(lm.init, static_argnums=0)(cfg, jax.random.key(seed))
    rng = np.random.default_rng(seed)
    cache_len = prompt_len + gen
    ann = jax.profiler.TraceAnnotation
    admit_span, prefill_span, decode_span, read_span = SPANS

    with ann(admit_span):
        prompts = rng.integers(0, cfg.vocab_size, size=(batch, prompt_len),
                               dtype=np.int32)
        state = lm.decode_state_init(cfg, batch, cache_len)
    decode_fn = make_decode_fn(cfg)

    @jax.jit
    def pick(logits, j):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(jax.random.key(j),
                                      logits).astype(jnp.int32)

    def pos_at(i):
        return jnp.full((batch,), i, jnp.int32)

    t0 = time.perf_counter()
    warm, _ = decode_fn(params, state, jnp.asarray(prompts[:, :1]), pos_at(0))
    jax.block_until_ready(pick(warm, 0))
    t_compile = time.perf_counter() - t0

    # prefill via decode steps (teacher-forcing the prompt) — exercises the
    # cache write path end to end; a fused prefill kernel is the TPU path.
    t0 = time.perf_counter()
    logits = None
    with ann(prefill_span):
        for i in range(prompt_len):
            tok = jnp.asarray(prompts[:, i:i + 1])
            logits, state = decode_fn(params, state, tok, pos_at(i))
        jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    out_tokens = np.zeros((batch, gen), np.int32)
    kept = []
    t0 = time.perf_counter()
    for j in range(gen):
        if j < keep_logits:
            kept.append(logits)
        nxt = pick(logits, j)
        with ann(read_span):
            out_tokens[:, j] = np.asarray(nxt)
        with ann(decode_span):
            logits, state = decode_fn(params, state, nxt[:, None],
                                      pos_at(prompt_len + j))
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    tput = batch * gen / max(t_decode, 1e-9)
    if not quiet:
        print(f"[serve] batch={batch} compile {t_compile:.2f}s | prefill "
              f"{prompt_len} tok in {t_prefill:.2f}s | decode {gen} tok in "
              f"{t_decode:.2f}s ({tput:.1f} tok/s)")
    return {"tokens": out_tokens, "prompts": prompts, "logits": kept,
            "decode_tok_per_s": tput, "compile_s": t_compile,
            "prefill_s": t_prefill, "decode_s": t_decode}


def serve_distributed(*, ranks: int = 3, sessions: int = 32,
                      rate: float = 400.0, seed: int = 0,
                      quiet: bool = False) -> dict:
    """Run the multi-rank serve tier (router + workers over one Comm)
    and return the router's report. Thin wrapper over
    ``repro.serve.run_serve`` so launch scripts and the jax path share
    one entry point."""
    from repro.serve import ServeConfig, run_serve
    cfg = ServeConfig(sessions=sessions, rate=rate, seed=seed)
    reports = run_serve(cfg, ranks=ranks)
    router = reports[0]
    if not quiet:
        print(f"[serve] {router['sessions']} sessions on {ranks} ranks "
              f"({ranks - 1} workers): qps {router['qps']:.1f}, "
              f"p50 {router['p50_us']:.0f} us, "
              f"p99 {router['p99_us']:.0f} us")
    return router


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--preset", default="cpu-smoke",
                    choices=["cpu-smoke", "full"])
    ap.add_argument("--ranks", type=int, default=0,
                    help="> 1: run the distributed serve tier instead "
                         "of the single-process jax driver")
    ap.add_argument("--sessions", type=int, default=32)
    ap.add_argument("--rate", type=float, default=400.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.ranks > 1:
        serve_distributed(ranks=args.ranks, sessions=args.sessions,
                          rate=args.rate, seed=args.seed)
        return
    if args.arch is None:
        ap.error("--arch is required for the single-process driver")
    cfg = get_config(args.arch)
    if args.preset == "cpu-smoke":
        cfg = cfg.reduced()
    serve_batch(cfg, batch=args.batch, prompt_len=args.prompt_len,
                gen=args.gen)


if __name__ == "__main__":
    main()
