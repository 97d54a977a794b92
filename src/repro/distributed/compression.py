"""Gradient compression for the expensive cross-pod hop.

cMPI's lesson is that the thin fabric (CXL link there, DCN/pod axis here)
must carry as few bytes as possible. After the in-pod reduce-scatter, each
device owns 1/|data| of the gradient; the cross-pod exchange of that shard
is further compressed bf16 -> int8 with a per-block scale (block = last
axis), cutting cross-pod wire bytes ~2x vs bf16 (4x vs f32).

Summation of int8 across pods happens in int32 (psum of the quantized
values), then one rescale — this keeps the collective itself integer and
exact; the only error is the quantization, bounded by scale/2 per element
and pod.
Error feedback (residual carry) is provided for training-quality use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def int8_encode(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """-> (q int8, scale f32 per last-axis block)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_decode(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def quantize_error(x: jax.Array) -> jax.Array:
    q, s = int8_encode(x)
    return x.astype(jnp.float32) - int8_decode(q, s)


def psum_int8(x: jax.Array, axis_name: str) -> jax.Array:
    """Compressed psum over `axis_name` (call inside shard_map). Members
    first agree on the largest per-block scale (one f32 per block),
    quantize their blocks to int8 codes with that shared scale, and sum
    the codes exactly in int32; a shared scale is what lets one factor
    rescale the sum. Error per element is at most (axis size) * scale / 2.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    smax = jnp.maximum(lax.pmax(amax, axis_name) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / smax), -127, 127)
    qsum = lax.psum(q.astype(jnp.int32), axis_name)
    return (qsum.astype(jnp.float32) * smax).astype(x.dtype)


class ErrorFeedback:
    """Residual carry: feed quantization error into the next step's grads.
    state = pytree of residuals matching the grad tree."""

    @staticmethod
    def init(grads):
        return jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    @staticmethod
    def apply(grads, residual):
        """-> (compensated grads, fn(compressed) -> new residual)."""
        comp = jax.tree.map(
            lambda g, r: g.astype(jnp.float32) + r, grads, residual)

        def new_residual(compressed):
            return jax.tree.map(
                lambda c, dec: c - dec.astype(jnp.float32),
                comp, compressed)

        return comp, new_residual
