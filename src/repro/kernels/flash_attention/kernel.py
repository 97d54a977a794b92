"""Pallas flash attention (causal, GQA) — online-softmax tiling for VMEM.

Grid (B, H, nq, nk); the kv axis is the innermost (sequential) dimension so
the f32 accumulator + running (m, l) live in VMEM scratch across kv steps
and the output block is written once on the last kv step. Causal blocks
strictly above the diagonal are skipped with @pl.when (zero work, not just
masking). GQA is free: the k/v BlockSpec index_map sends query head h to
kv head h // (H // KV), so no repeated-KV materialization in HBM.

VMEM per step: q(bq,d) + k(bk,d) + v(bk,d) in compute dtype + f32
acc(bq,d) + m,l(bq) — with bq=bk=128, d=128, bf16: ~160 KB. MXU dims are
128-aligned by construction.

Target numerics match the jnp oracle: scores f32, exp in f32, accumulate
f32, final out cast to q.dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_body(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, bq: int, bk: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv block strictly above the q block's diagonal does no work
    run = (not causal) or (ki * bk <= qi * bq + (bq - 1))

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                        # (bq, d)
        k = k_ref[0, 0]                        # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, H, S, D); k, v: (B, KV, S, D) with H % KV == 0.
    Returns (B, H, S, D) in q.dtype. ``interpret=None`` compiles on a TPU
    and interprets elsewhere."""
    b, h, s, d = q.shape
    kv = k.shape[1]
    assert h % kv == 0, (h, kv)
    group = h // kv
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = 1.0 / math.sqrt(d)

    body = functools.partial(_flash_body, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        body,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            # f32 accumulator + running max / normalizer across kv steps
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
