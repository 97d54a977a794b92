"""Pallas cell-copy kernel — the TPU reading of cMPI's data plane.

cMPI's hot loop is the CPU ``mov``-driven copy of message cells between a
local buffer and the CXL pool, with a coherence epilogue per cell
(paper §3.3, §4.3). On TPU the analogue of the 'message cell' is the VMEM
block: HBM -> VMEM -> HBM chunked copy, double-buffered by the Pallas
pipeline across grid steps, with a fused per-cell checksum standing in for
the header/validity epilogue (so the consumer can verify a cell without a
second pass over HBM).

The BlockSpec cell shape is the tunable that reproduces the paper's Fig-9
cell-size study as a TPU block-shape sweep (benchmarks/fig9_cellsize.py):
too-small cells waste pipeline latency per cell, too-large cells overflow
VMEM — same tradeoff, different memory hierarchy.

Layout: messages are (n_cells, cell_bytes/4) int32 words, cell rows 128-
word aligned (the MXU/VPU lane width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

LANE = 128


def _cellcopy_body(src_ref, dst_ref, sum_ref):
    """One grid step: copy `block_cells` cells and emit their checksums."""
    data = src_ref[0]                         # (block_cells, words) int32
    dst_ref[0] = data
    # wrapping 32-bit sum per cell — the validity word the consumer checks.
    # Mosaic reduces no unsigned integers; an int32 sum wraps to the same
    # bits, and the wrapper bitcasts it to uint32.
    s = jnp.sum(data, axis=1, dtype=jnp.int32)          # (block_cells,)
    sum_ref[...] = s.reshape(sum_ref.shape)


@functools.partial(jax.jit, static_argnames=("block_cells", "interpret"))
def cellcopy(src: jax.Array, *, block_cells: int = 8,
             interpret: bool | None = None):
    """Copy (n_cells, words) int32 cells; returns (dst, checksums u32).

    ``block_cells`` cells ride one VMEM block per grid step; the Pallas
    pipeline double-buffers the HBM->VMEM->HBM stream across steps.
    ``interpret=None`` compiles on a TPU and interprets elsewhere.

    The kernel sees the cells as (n_blocks, block_cells, words) and the
    checksums as (n_blocks, 1, block_cells): each block's last two dims
    equal the array's, which Mosaic accepts for any ``block_cells`` (a
    (block_cells, words) block of the flat array needs a multiple of 8,
    and a rank-1 (block_cells,) block a multiple of 128).
    """
    n_cells, words = src.shape
    assert n_cells % block_cells == 0, (n_cells, block_cells)
    assert words % LANE == 0, f"cell words {words} not {LANE}-aligned"
    n_blocks = n_cells // block_cells
    dst, sums = pl.pallas_call(
        _cellcopy_body,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, block_cells, words), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, block_cells, words), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_cells), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, block_cells, words), src.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1, block_cells), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
    )(src.reshape(n_blocks, block_cells, words))
    return (dst.reshape(n_cells, words),
            jax.lax.bitcast_convert_type(sums.reshape(n_cells), jnp.uint32))


def vmem_bytes(block_cells: int, words: int) -> int:
    """VMEM working set claimed by one grid step (src + dst blocks,
    double-buffered by the pipeline => x2)."""
    return 2 * 2 * block_cells * words * 4
