"""Pallas chunked WKV6 (RWKV-6 'Finch') kernel.

Recurrence (per head, n = head size):
    S_t = diag(w_t) S_{t-1} + k_t v_t^T          (state n x n)
    o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

The sequential oracle (models/blocks._wkv6_scan) is O(S) steps of rank-1
updates — latency-bound on any hardware. The chunked-parallel form turns a
chunk of L tokens into dense L x n / n x n matmuls (MXU food):

  with P_t = prod_{j<=t} w_j (per-channel cumulative decay inside a chunk),
    o_t   = (r_t * P_{t-1}) S_0                       <- inter-chunk
          + sum_{i<t} [(r_t * P_{t-1}/P_i) . k_i] v_i <- intra-chunk
          + (r_t * u . k_t) v_t                       <- current token
    S_L   = diag(P_L) S_0 + sum_i (P_L / P_i * k_i) v_i^T

Grid (B, H, n_chunks): the chunk axis is innermost/sequential, so the f32
state S (stored transposed) rides in VMEM scratch across chunk steps — the
standard Pallas carry pattern. P comes from a cumulative sum of log w
(a triangular matmul: Mosaic lowers no cumprod). L is kept small (32) so
the decay ratios P/P_i stay in f32 range (w in (0,1); worst case w^-L).

All math f32; inputs (r, k, v, w) are pre-projected (B, H, S, n) tensors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _dot(a, b, contract):
    """f32 matmul at full precision (the default may round to bf16)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _wkv6_body(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, L: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)       # (L, n)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    logw = jnp.log(w_ref[0, 0].astype(jnp.float32))   # decay in (0, 1)
    u = u_ref[0].astype(jnp.float32)          # (1, n)
    St = s_ref[...]                           # (n, n) = S0^T: [value, key]

    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    ij = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # log P_t = sum_{j<=t} log w_j as a lower-triangular matmul (Mosaic
    # lowers no cumprod/cumsum); log P_{t-1} = log P_t - log w_t
    logP = _dot(jnp.where(ij <= ti, 1.0, 0.0), logw, ((1,), (0,)))
    rP = r * jnp.exp(logP - logw)             # r_t * P_{t-1}
    # inter-chunk: (r_t * P_{t-1}) @ S0
    o = _dot(rP, St, ((1,), (1,)))
    # intra-chunk: att[t, i] = sum_c rP[t,c] * (k[i,c] / P[i,c]),  i < t
    att = _dot(rP, k * jnp.exp(-logP), ((1,), (1,)))    # (L, L)
    att = jnp.where(ij < ti, att, 0.0)        # strictly lower triangular
    o = o + _dot(att, v, ((1,), (0,)))
    # current token bonus: (r_t * u . k_t) v_t
    o = o + jnp.sum(r * u * k, axis=1, keepdims=True) * v
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state update: S_L = diag(P_L) S0 + sum_i ((P_L / P_i) * k_i) v_i^T,
    # kept transposed so diag(P_L) scales lanes and needs no relayout
    logPL = logP[L - 1:L]                     # (1, n)
    kS = jnp.exp(logPL - logP) * k            # (L, n)
    s_ref[...] = jnp.exp(logPL) * St + _dot(v, kS, ((0,), (0,)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, *, chunk: int = 32,
         interpret: bool | None = None) -> jax.Array:
    """r,k,v,w: (B, H, S, n); u: (H, n). Returns (B, H, S, n) f32.

    ``u`` enters the kernel as (H, 1, n) so that its (1, 1, n) block ends
    in the array's own last two dims, as Mosaic requires (a (1, n) block
    of an (H, n) array is refused). ``interpret=None`` compiles on a TPU
    and interprets elsewhere."""
    b, h, s, n = r.shape
    L = min(chunk, s)
    assert s % L == 0, (s, L)
    nc = s // L
    body = functools.partial(_wkv6_body, L=L)
    return pl.pallas_call(
        body,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, n), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, L, n), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, L, n), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, L, n), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, n), lambda b_, h_, c: (h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, L, n), lambda b_, h_, c: (b_, h_, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(r, k, v, w, u.reshape(h, 1, n))
