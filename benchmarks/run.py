"""Benchmark harness entrypoint: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table1 ...]

Writes CSVs under artifacts/bench/ and prints per-benchmark summaries.
The roofline section reads the dry-run artifacts (run
``python -m repro.launch.dryrun`` first for the full 80-cell table).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (fig5_8_osu, fig9_cellsize, fig10_scaling,
                        fig11_coherence, roofline, table1_interconnects)

BENCHES = {
    "table1": table1_interconnects.main,
    "fig5_8": fig5_8_osu.main,
    "fig9": fig9_cellsize.main,
    "fig10": fig10_scaling.main,
    "fig11": fig11_coherence.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()

    names = args.only or list(BENCHES) + ["roofline"]
    # fig9's kernel sweep initialises a JAX backend; a rank process forked
    # after that would inherit the parent's device client (on a TPU host,
    # the chip), so fig9 runs after every benchmark that forks
    names = sorted(names, key=lambda n: n == "fig9")
    failures = []
    for name in names:
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)))
        t0 = time.perf_counter()
        try:
            if name == "roofline":
                rows = roofline.run()
                ok = [r for r in rows if r[3] not in ("SKIP", "FAIL")]
                skip = [r for r in rows if r[3] == "SKIP"]
                fail = [r for r in rows if r[3] == "FAIL"]
                print(f"roofline cells: {len(ok)} ok, {len(skip)} skip, "
                      f"{len(fail)} fail (CSV: artifacts/bench/"
                      f"roofline_baseline.csv)")
                if fail:
                    failures.append(name)
            else:
                BENCHES[name](quick=args.quick)
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"--- {name} done in {time.perf_counter() - t0:.1f}s")
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        sys.exit(1)
    print("\nall benchmarks completed; CSVs in artifacts/bench/")


if __name__ == "__main__":
    main()
