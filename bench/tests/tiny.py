"""Tiny cells for the CPU tests: the benchmark's own drivers, references
and harness on a reduced configuration, with the timed path optionally
broken underneath.

    python3 bench/tests/tiny.py <serve|train> <seed> [--fault NAME]
        [--calibrate]

Prints the harness's result line (or, with ``--calibrate``, the
calibration readings). Training needs four devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu``.
"""
import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

STARTED = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

SMALL = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, intermediate_size=64,
             vocab_size=128)
OVERRIDES = {"n_layers": 2, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
             "d_head": 8, "d_ff": 64, "vocab_size": 128, "remat": "none"}
# readings at this size, on seeds 1-5 (program / control / faults):
# serving mean gap (seeds 1-7 and 2**31 + 12345) 0-0.00085 / 0.0059-0.0151;
# training loss_gap ~2e-5 / ~1e-4 /
# >2e-3, first_grad_gap ~1.5e-3 / ~4e-2 / >0.3, update_gap ~1.7e-3 /
# ~6e-3 / >0.06
SERVE_LIMITS = {"served_gap_mean": 0.002}
TRAIN_LIMITS = {"loss_gap": 5e-4, "first_grad_gap": 0.01,
                "update_gap": 0.01}


def specs(kind: str) -> Path:
    """A spec directory holding one tiny cell ``w``."""
    tmp = Path(tempfile.mkdtemp(prefix="bench-tiny-"))
    for d in ("workloads", "traffic", "configs"):
        (tmp / d).mkdir()
    if kind == "serve":
        c = json.loads((BENCH / "configs/granite-moe-1b-a400m.json")
                       .read_text())
        c.update(SMALL, num_local_experts=4, num_experts_per_tok=2)
        c["overrides"] = dict(c["overrides"], **OVERRIDES,
                              **{"moe.n_experts": 4,
                                 "moe.top_k": 2,
                                 "moe.capacity_factor": 2.0})
        t = json.loads((BENCH / "traffic/static_b128_p128_o128.json")
                       .read_text())
        t.update(slots=8, slot_ctx=32, check_requests=8, limits=SERVE_LIMITS)
        t["requests"].update(prompt_len=8, output_len=16)
        chips = 1
    else:
        c = json.loads((BENCH / "configs/smollm-135m.json").read_text())
        c.update(SMALL)
        c["overrides"] = OVERRIDES
        t = {"driver": "train_dp",
             "mesh": {"shape": [2, 2], "axes": ["pod", "data"]},
             "compression": "none", "seq_len": 32, "global_batch": 16,
             "trace_first_step": 20, "trace_steps": 3,
             "reference_rows_per_call": 4, "limits": TRAIN_LIMITS}
        chips = 4
    w = {"config": "cfg", "traffic": "tr", "chips": chips, "why": "tiny"}
    for kind_dir, obj in (("configs/cfg", c), ("traffic/tr", t),
                          ("workloads/w", w)):
        (tmp / f"{kind_dir}.json").write_text(json.dumps(obj))
    return tmp


def plant(fault: str) -> None:
    """Break the timed path underneath the benchmark."""
    from repro.distributed import schedules
    from repro.models import lm
    from repro.train import optimizer as opt
    decode_step, loss_fn = lm.decode_step, lm.loss_fn
    if fault == "state_unchanged.serve":
        def broken(params, cfg, state, batch, pos, **kw):
            logits, _ = decode_step(params, cfg, state, batch, pos, **kw)
            return logits, state
        lm.decode_step = broken
    elif fault == "token_altered":
        def broken(params, cfg, state, batch, pos, **kw):
            logits, new = decode_step(params, cfg, state, batch, pos, **kw)
            return logits.at[:, 3].add(1e3), new
        lm.decode_step = broken
    elif fault == "state_unchanged.train":
        opt.apply_updates = lambda oc, params, grads, state: (
            params, state, {"grad_norm": opt.global_norm(grads),
                            "lr": opt.lr_at(oc, state["count"])})
    elif fault == "half_batch":
        def broken(params, cfg, batch, **kw):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return loss_fn(params, cfg, half, **kw)
        lm.loss_fn = broken
    elif fault == "no_exchange":
        schedules.sync_grads = lambda grads, **kw: grads
    else:
        raise ValueError(fault)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("kind", choices=("serve", "train"))
    ap.add_argument("seed", type=int)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--calibrate", action="store_true")
    args = ap.parse_args()
    import jax
    from bench import calibrate, harness
    jax.config.update("jax_enable_compilation_cache", False)
    if args.fault:
        plant(args.fault)
    run = harness.Run("w", args.seed, 2.0, False, STARTED,
                      require_tpu=False, spec_dir=specs(args.kind))
    if args.calibrate:
        run.claim_devices()
        cfg = run.program_config()
        if args.kind == "serve":
            calibrate.serve_readings(run, cfg, [args.seed], 1)
        else:
            calibrate.train_readings(run, cfg, [args.seed], 1, True)
        return 0
    harness.report(harness.execute(run))
    return 0


if __name__ == "__main__":
    sys.exit(main())
