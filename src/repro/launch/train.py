"""Training driver.

CPU preset runs a REDUCED config end-to-end (real training, synthetic
Markov data, checkpoint/restart, straggler monitor); on a TPU pod the same
driver takes the full config + production mesh.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 60 --preset cpu-smoke
  PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
      --steps 30 --preset cpu-smoke --cmpi-sync int8
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import SHAPES, get_config
from repro.configs.base import InputShape
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.train import data as D
from repro.train import optimizer as opt
from repro.train import steps as ST
from repro.train.checkpoint import CheckpointManager
from repro.train.fault import FailureInjector, HeartbeatBoard


def run_training(cfg, shape: InputShape, steps: int, *,
                 ckpt_dir: str | Path | None = None,
                 ckpt_every: int = 20,
                 seed: int = 0,
                 injector: FailureInjector | None = None,
                 log_every: int = 10,
                 grad_accum: int = 1,
                 n_shards: int = 1,
                 quiet: bool = False) -> dict:
    """Single-process training loop (mesh-free CPU path). Returns final
    metrics + loss history. Restartable via ckpt_dir."""
    oc = opt.for_model(cfg)
    params = lm.init(cfg, jax.random.key(seed))
    opt_state = opt.init(oc, params)
    start_step = 0
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if mgr is not None:
        got = mgr.restore((params, opt_state))
        if got[0] is not None:
            start_step, (params, opt_state) = got
            if not quiet:
                print(f"[train] resumed from step {start_step}")

    ds = D.SyntheticLM(D.for_model(cfg, shape, seed))
    board = HeartbeatBoard(n_shards)

    @jax.jit
    def step_fn(params, opt_state, batch):
        def loss_fn(p):
            return lm.loss_fn(p, cfg, batch)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_p, new_o, om = opt.apply_updates(oc, params, grads, opt_state)
        return new_p, new_o, dict(metrics, **om)

    history = []
    t0 = time.perf_counter()
    for step in range(start_step, steps):
        if injector is not None:
            injector.check(step)
        batch = {k: jax.numpy.asarray(v)
                 for k, v in ds.batch(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        history.append(loss)
        board.beat(0, step)
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save_async(step + 1, (params, opt_state))
        if not quiet and (step % log_every == 0 or step == steps - 1):
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):7.3f} "
                  f"lr {float(metrics['lr']):.2e}")
    if mgr is not None:
        mgr.save(steps, (params, opt_state))
        mgr.wait()
    dt = time.perf_counter() - t0
    tokens = (steps - start_step) * shape.global_batch * shape.seq_len
    return {
        "history": history,
        "final_loss": history[-1] if history else float("nan"),
        "tokens_per_s": tokens / max(dt, 1e-9),
        "params": params,
        "opt_state": opt_state,
        "health": board.health(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--preset", default="cpu-smoke",
                    choices=["cpu-smoke", "full"])
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    if args.preset == "cpu-smoke":
        cfg = cfg.reduced()
        shape = dataclasses.replace(shape, seq_len=args.seq_len,
                                    global_batch=args.global_batch)
    out = run_training(cfg, shape, args.steps, ckpt_dir=args.ckpt_dir,
                       seed=args.seed)
    uniform = float(np.log(cfg.vocab_size))
    print(f"[train] done: final loss {out['final_loss']:.4f} "
          f"(uniform {uniform:.2f}) | {out['tokens_per_s']:.0f} tok/s")


if __name__ == "__main__":
    main()
