#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; the benchmark's
own runs do not make them.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \\
        [--control-seeds 3] [--faults]

One process, the cell's own sizes, the path the window drives. For each
seed: weights from the seed, then

- serving: one batch of the cell's traffic served to its end; on the
  requests the check samples, the program's ``served_logit_gap``, and the
  control's: the reference in float8 put in the program's place, the gap
  under the float32 reference of the token it puts first at each position;
- training: the first three steps of the program; its three numbers
  against the float32 reference, and for the first ``--control-seeds``
  seeds the control's (the reference in float8) and, with ``--faults``,
  those of the faults planted in the reference: half of the batch left out
  (mean over the rest) and the exchange between chips left out (the first
  chip's rows alone, divided by the chip count as the step divides them).

Prints one JSON line per seed and reading.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def gap_stats(gaps):
    import numpy as np
    return {"max": float(gaps.max()), "mean": float(gaps.mean()),
            "p99": float(np.percentile(gaps, 99)),
            "off_best": float(np.mean(gaps > 0))}


def serve_readings(run, cfg, seeds, control_seeds):
    import numpy as np
    from bench.drivers import serve_static as S
    server = S.Server(run, cfg)
    for n, seed in enumerate(seeds):
        run.seed = seed
        params = run.make_params(cfg)
        server.warm(params)
        reqs = S.batch_requests(run, 0, server.slots, cfg.vocab_size)
        _, _, out, _ = server.serve(params, reqs)
        sample = S.check_sample(run, list(zip(reqs, out)))
        served = np.stack([t for _, t in sample])
        f32 = S.reference_logits(run, params, sample, server.plen,
                                 server.olen)
        row = {"seed": seed, "program": gap_stats(S.token_gaps(f32, served))}
        if n < control_seeds:
            f8 = S.reference_logits(run, params, sample, server.plen,
                                    server.olen, fp8=True)
            row["control"] = gap_stats(S.token_gaps(
                f32, np.asarray(f8.argmax(-1))))
        print(json.dumps(row), flush=True)
        del params, f32


def train_readings(run, cfg, seeds, control_seeds, faults):
    from bench.drivers import train_dp as T
    trainer = T.Trainer(run, cfg)
    gb, chips = trainer.GB, len(run.devices)
    for n, seed in enumerate(seeds):
        run.seed = seed
        params, opt_state, feeder = trainer.start()
        try:
            params, opt_state, seen = trainer.first_steps(params, opt_state,
                                                          feeder)
        finally:
            T.stop(feeder)
        del params, opt_state
        batches = seen["host_batches"]
        f32 = T.reference_run(run, cfg, batches)
        readings = {"program": seen}
        if n < control_seeds:
            # the reference, so varied, in the program's place
            variants = {"control": {"fp8": True}}
            if faults:
                variants["half_batch"] = {"grad_rows": slice(0, gb // 2)}
                variants["no_exchange"] = {"grad_rows": slice(0, gb // chips),
                                           "grad_scale": 1.0 / chips}
            for name, v in variants.items():
                readings[name] = T.reference_run(run, cfg, batches, **v)
        for name, got in readings.items():
            checks = T.compare(got, f32, run.traffic["limits"])
            print(json.dumps({"seed": seed, "reading": name,
                              **{c.name: c.value for c in checks}}),
                  flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = harness.Run(args.workload, args.seeds[0], 0.0, False,
                      started=STARTED)
    try:
        run.claim_devices()
    except harness.NoDevice as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 3
    cfg = run.program_config()
    if run.traffic["driver"] == "serve_static":
        serve_readings(run, cfg, args.seeds, args.control_seeds)
    else:
        train_readings(run, cfg, args.seeds, args.control_seeds,
                       args.faults)
    print(f"[calibrate] {time.perf_counter() - STARTED:.1f}s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
