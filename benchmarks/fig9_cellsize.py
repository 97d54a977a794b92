"""Fig 9: two-sided bandwidth vs message size for varying message-CELL
sizes (16/32/64/128 KB).

measured: the real cMPI SPSC queues between two processes — the mechanism
          (messages larger than a cell are chunked; bigger cells amortize
          per-cell overhead until a plateau) is what the paper tunes.
modeled : per-cell overhead model at CXL constants showing the paper's
          threshold: default 16 KB caps bandwidth, 64 KB lifts the peak,
          beyond 64 KB no further gain.
kernel  : the TPU reading — the cellcopy Pallas kernel's block-shape sweep
          (cells-per-VMEM-block), wall time of one call; the row label
          names the backend and whether Mosaic compiled the kernel or the
          Pallas interpreter ran it (relative numbers only when
          interpreted).
"""
from __future__ import annotations

import time

from benchmarks.common import shm_bandwidth, write_csv
from repro.perfmodel.interconnects import CXL_SHM

KB = 1024
MiB = 1024 * 1024
CELLS = [16 * KB, 32 * KB, 64 * KB, 128 * KB]
MSGS = [4 * KB, 16 * KB, 64 * KB, 256 * KB, 1024 * KB]

T_CELL = 2.2e-6          # per-cell enqueue overhead (coherence epilogue)
_CELL_HALF = 24 * KB     # cell size at which the queue pipeline reaches
#                          half of fabric peak (calibrated to Fig 9)
_TWOSIDED_CEIL = 6.33e9  # ~6,050 MiB/s: the double-copy ceiling (paper)


def modeled_bw(msg: int, cell: int, procs: int = 32) -> float:
    """Chunked-transfer model: per message ceil(msg/cell) cells, each
    paying T_CELL + copy; small cells additionally throttle the queue
    pipeline (more head/tail round trips per byte), which is what makes
    the 16 KB default cap bandwidth and 64 KB lift it (Fig 9)."""
    n_cells = -(-msg // cell)
    t = n_cells * T_CELL + msg / CXL_SHM.bandwidth \
        * CXL_SHM._contention(msg, procs)
    agg = procs * msg / t * 0.70          # two-sided double-copy factor
    pipeline_cap = (CXL_SHM.fabric_peak * 1.073  # GiB->GB constant
                    * cell / (cell + _CELL_HALF))
    return min(agg, pipeline_cap, _TWOSIDED_CEIL)


def run(quick: bool = False) -> list[list]:
    rows = []
    for cell in CELLS:
        for msg in MSGS:
            rows.append(["modeled", cell // KB, msg // KB,
                         f"{modeled_bw(msg, cell) / MiB:.0f}"])
    # measured: real SPSC queues, cell size swept
    msizes = [16 * KB, 256 * KB] if quick else [16 * KB, 64 * KB, 256 * KB]
    iters = 4 if quick else 12
    for cell in ([16 * KB, 64 * KB] if quick else CELLS):
        bw = shm_bandwidth(msizes, iters=iters, cell_size=cell, window=8)
        for msg in msizes:
            rows.append(["measured", cell // KB, msg // KB,
                         f"{bw[msg] / MiB:.0f}"])
    # kernel block sweep (TPU cell == VMEM block)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import interpret_mode
    from repro.kernels.cellcopy.kernel import cellcopy
    mode = "interp" if interpret_mode(None) else "compiled"
    label = f"kernel_{mode}_{jax.default_backend()}"
    src = jnp.asarray(np.arange(64 * 2048, dtype=np.int32)
                      .reshape(64, 2048))
    for bc in (1, 4, 16, 64):
        f = lambda: cellcopy(src, block_cells=bc)[0].block_until_ready()
        f()
        t0 = time.perf_counter()
        for _ in range(3):
            f()
        dt = (time.perf_counter() - t0) / 3
        rows.append([label, bc * 8, 512, f"{dt * 1e3:.1f}ms"])
    write_csv("fig9_cellsize",
              ["kind", "cell_KB|block", "msg_KB", "bw_MiB_s|time"], rows)
    return rows


def main(quick: bool = False) -> None:
    rows = run(quick)
    best = {}
    for r in rows:
        if r[0] == "modeled":
            best.setdefault(r[1], 0)
            best[r[1]] = max(best[r[1]], float(r[3]))
    print("modeled peak two-sided bw by cell size:",
          {f"{k}KB": f"{v:.0f}MiB/s" for k, v in best.items()},
          "(paper: 16KB -> ~3600, 64KB -> ~6000, no gain beyond)")


if __name__ == "__main__":
    main()
