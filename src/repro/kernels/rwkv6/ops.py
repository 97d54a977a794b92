"""Public wrapper matching models/blocks layout: (B, S, H, n) tensors."""
from __future__ import annotations

import jax

from repro.kernels.rwkv6.kernel import wkv6


def wkv6_bshn(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, *, chunk: int = 32,
              interpret: bool | None = None) -> jax.Array:
    """r,k,v,w: (B, S, H, n); u: (H, n) -> (B, S, H, n) f32
    (the models/blocks._wkv6_scan layout)."""
    args = [a.transpose(0, 2, 1, 3) for a in (r, k, v, w)]
    out = wkv6(*args, u, chunk=chunk, interpret=interpret)
    return out.transpose(0, 2, 1, 3)
