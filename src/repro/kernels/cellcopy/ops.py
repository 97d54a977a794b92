"""jit'd public wrapper for the cellcopy kernel: message-buffer in/out with
padding to lane alignment, plus verification helper."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cellcopy.kernel import LANE, cellcopy


def copy_message(buf: np.ndarray | jax.Array, cell_bytes: int = 16384, *,
                 block_cells: int = 8, interpret: bool | None = None):
    """Copy a flat uint8 message through cell-granular kernel DMA.

    Returns (copied uint8 array of the original length, checksums)."""
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    n = buf.shape[0]
    words_per_cell = cell_bytes // 4
    words_per_cell += (-words_per_cell) % LANE
    cell_bytes = words_per_cell * 4
    n_cells = -(-n // cell_bytes)
    n_cells += (-n_cells) % block_cells
    pad = n_cells * cell_bytes - n
    flat = jnp.pad(buf, (0, pad))
    cells = flat.view(jnp.int32).reshape(n_cells, words_per_cell)
    dst, sums = cellcopy(cells, block_cells=block_cells, interpret=interpret)
    out = dst.reshape(-1).view(jnp.uint8)[:n]
    return out, sums


def verify(cells: jax.Array, sums: jax.Array) -> jax.Array:
    """Consumer-side validity check (what the header word buys us)."""
    expect = jnp.sum(cells.astype(jnp.uint32), axis=1, dtype=jnp.uint32)
    return jnp.all(expect == sums)
