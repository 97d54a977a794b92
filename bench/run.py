#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

from the root of a checkout. The cell is ``bench/workloads/<cell>.json``
(see ``bench/harness.py`` for how the rest is found). ``--trace 0`` prints
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of part of the window. The last line of standard output is
the result, one JSON object; the numbers compared for ``correct`` are the
last lines of standard error and the last key of the result.

Exits 3 and prints no result where JAX finds no TPU, or fewer chips than
the cell asks for; exits 2 where the program (``src/``) or a file of the
cell is missing. JAX's persistent compilation cache is the program's
(``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` where set,
else ``.jax_cache/`` in the checkout.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

# The TPU runtime pins a host staging buffer at start, 4 GiB by default;
# without transparent hugepages that takes seconds, and most of the spread
# of set-up. A window moves a few hundred bytes a step between host and
# device, so a smaller buffer serves it. Set before JAX loads the runtime.
STAGING_BYTES = str(64 << 20)
os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", STAGING_BYTES)
os.environ.setdefault("TPU_PREMAPPED_BUFFER_TRANSFER_THRESHOLD_BYTES",
                      STAGING_BYTES)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from bench import harness
    try:
        from repro.launch.compile_cache import enable_compile_cache
        run = harness.Run(args.workload, args.seed, args.seconds,
                          bool(args.trace), started=STARTED)
    except (ImportError, FileNotFoundError) as e:
        print(f"[bench] cannot run {args.workload}: {e}", file=sys.stderr)
        return 2
    import jax
    enable_compile_cache()
    # every program goes to the cache, so that only a checkout's first
    # run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = harness.execute(run)
    except harness.NoDevice as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
