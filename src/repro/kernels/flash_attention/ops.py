"""Public wrapper: (B, S, H, D)-layout attention entry point matching
models/blocks.py conventions, dispatching to the Pallas kernel.

On a real TPU ``interpret=False`` compiles the kernel; in this container
(CPU) interpret mode executes the same kernel body for validation.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention.kernel import flash_attention


def flash_attention_bshd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, block_q: int = 128,
                         block_k: int = 128,
                         interpret: bool | None = None) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, S, KV, D) — the blocks.py layout."""
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = flash_attention(qh, kh, vh, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return out.transpose(0, 2, 1, 3)
