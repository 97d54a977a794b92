"""Model building blocks: norms, rotary embeddings, attention (GQA, causal,
chunked/memory-efficient, decode-with-cache), cross-attention, SwiGLU FFN,
capacity-based MoE, Mamba selective scan, RWKV6 (Finch) time/channel mix.

All blocks are pure functions  ``apply(params, x, ...) -> y``  with explicit
parameter pytrees; initialization lives next to application so
``jax.eval_shape(init)`` gives allocation-free parameter specs for the
dry-run. Everything is written against a 16-way tensor-parallel axis in mind:
projection output dims are flattened (n_heads * d_head) so TP sharding does
not depend on head-count divisibility.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, MambaConfig, RWKVConfig

Params = dict[str, Any]


# --------------------------------------------------------------------------
# small helpers
# --------------------------------------------------------------------------

def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# The sublayers of the serving step, as ``jax.named_scope`` names. The
# compiled program keeps each op's scope in its ``op_name`` metadata, and
# ``repro.analysis.hlo.op_scopes`` maps XLA's op names back to these.
SCOPES = ("embed", "attn/qkv", "attn/kv_write", "attn/core", "attn/out",
          "moe/route", "moe/dispatch", "moe/experts", "moe/combine", "head")
# nested inside a sublayer's scope, around a weight's cast to the compute
# dtype
CAST = "cast"


def scope(name: str):
    """The named scope of one sublayer of ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of {SCOPES}")
    return jax.named_scope(name)


def cast(w, dtype):
    """A weight in ``dtype``; the conversion is traced in a ``cast`` scope."""
    with jax.named_scope(CAST):
        return w.astype(dtype)


def dense_init(key, shape, scale: float | None = None, dtype=jnp.float32):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


def rmsnorm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x, positions, theta: float):
    """x: (..., S, n_heads, d_head); positions: (..., S) int32."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)                      # (d_head//2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                                 # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": dense_init(ks[0], (D, H * Dh), dtype=dt),
        "wk": dense_init(ks[1], (D, KV * Dh), dtype=dt),
        "wv": dense_init(ks[2], (D, KV * Dh), dtype=dt),
        "wo": dense_init(ks[3], (H * Dh, D), scale=1.0 / math.sqrt(H * Dh), dtype=dt),
    }


def _repeat_kv(k, n_rep: int):
    """(B, S, KV, Dh) -> (B, S, KV*n_rep, Dh) by head repetition (GQA)."""
    if n_rep == 1:
        return k
    b, s, kv, dh = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, dh))
    return k.reshape(b, s, kv * n_rep, dh)


def _plain_attention(q, k, v, causal: bool, q_offset=0, kv_len: Optional[jax.Array] = None):
    """q: (B,Sq,H,Dh)  k,v: (B,Sk,H,Dh). fp32 softmax."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        mask = kpos <= qpos
        scores = jnp.where(mask[None, None], scores, -1e30)
    if kv_len is not None:
        valid = jnp.arange(sk)[None, None, None, :] < kv_len[:, None, None, None]
        scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _decode_attention(q, k_cache, v_cache, kv_len):
    """One query row over the cache as it is stored, in grouped-query form.

    q: (B, 1, H, Dh); k_cache, v_cache: (B, KV, Smax, Dh); kv_len: (B,)
    valid positions. Query head h reads KV head h // (H // KV), as
    ``_repeat_kv`` maps them, but the cache is read in place: no
    transpose, no head repetition, no cast. fp32 scores and softmax.
    """
    b, _, h, dh = q.shape
    kv, smax = k_cache.shape[1], k_cache.shape[2]
    q = q.reshape(b, kv, h // kv, dh)
    scale = 1.0 / math.sqrt(dh)
    scores = jnp.einsum("bkgd,bksd->bkgs", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(smax)[None, None, None, :] < kv_len[:, None, None, None]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bksd->bkgd", probs, v_cache)
    return out.reshape(b, 1, h, dh)


def _chunked_attention(q, k, v, causal: bool, q_chunk: int, kv_chunk: int):
    """Memory-efficient (online-softmax) attention; never materializes SxS.

    This is the pure-jnp oracle mirrored by kernels/flash_attention. Causal
    masking is applied per (q-block, kv-block); kv-blocks strictly above the
    diagonal are skipped by construction of the scan bounds.
    """
    b, s, h, dh = q.shape
    sk = k.shape[1]
    assert s % q_chunk == 0 and sk % kv_chunk == 0
    nq, nk = s // q_chunk, sk // kv_chunk
    scale = 1.0 / math.sqrt(dh)

    q = q.reshape(b, nq, q_chunk, h, dh)
    k = k.reshape(b, nk, kv_chunk, h, dh)
    v = v.reshape(b, nk, kv_chunk, h, dh)

    def q_block(qi, qb):
        # qb: (B, q_chunk, H, Dh)
        def kv_step(carry, ki):
            acc, m, l = carry
            kb = k[:, ki]
            vb = v[:, ki]
            scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                                preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk)[:, None]
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
                scores = jnp.where((kpos <= qpos)[None, None], scores, -1e30)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, h, q_chunk, dh), jnp.float32)
        m0 = jnp.full((b, h, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        n_kv = (qi + 1) * q_chunk // kv_chunk if causal else nk
        # scan over every kv block but mask work above the diagonal; the
        # optimized path (flash kernel / block-skip) is a §Perf iteration.
        (acc, m, l), _ = lax.scan(kv_step, (acc0, m0, l0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3)  # (B, q_chunk, H, Dh)

    outs = lax.map(lambda i: q_block(i, q[:, i]), jnp.arange(nq))
    # outs: (nq, B, q_chunk, H, Dh) -> (B, S, H, Dh)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh).astype(q.dtype)


def attn_decode_readonly(params: Params, cfg: ModelConfig, x, kv_cache):
    """Cross-attention at decode time: q from x (B,1,D), k/v from the static
    ctx cache (B, KV, Nctx, Dh). No cache update, no causal mask."""
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    b, s, _ = x.shape
    cdt = _dtype(cfg)
    with scope("attn/qkv"):
        q = (x @ cast(params["wq"], cdt)).reshape(b, s, H, Dh)
    with scope("attn/core"):
        k = kv_cache["k"].transpose(0, 2, 1, 3)  # (B, Nctx, KV, Dh)
        v = kv_cache["v"].transpose(0, 2, 1, 3)
        k = _repeat_kv(k, H // KV)
        v = _repeat_kv(v, H // KV)
        out = _plain_attention(q, k, v, causal=False)
    with scope("attn/out"):
        return out.reshape(b, s, H * Dh) @ cast(params["wo"], cdt)


def attn_apply(params: Params, cfg: ModelConfig, x, positions, *,
               ctx=None, cache=None, cache_len=None, dist=None):
    """Self- or cross-attention.

    x: (B, S, D). ctx: (B, Nctx, D) for cross-attention.
    cache: optional dict {k: (B, KV, Smax, Dh), v: ...} for decode; when given,
    S must be 1 and `cache_len` (B,) gives the valid prefix length. Returns
    (out, new_cache).
    """
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    b, s, _ = x.shape
    cdt = _dtype(cfg)
    is_cross = ctx is not None
    with scope("attn/qkv"):
        q = (x @ cast(params["wq"], cdt)).reshape(b, s, H, Dh)
        kv_src = ctx if ctx is not None else x
        k = (kv_src @ cast(params["wk"], cdt)).reshape(b, -1, KV, Dh)
        v = (kv_src @ cast(params["wv"], cdt)).reshape(b, -1, KV, Dh)
        if not is_cross:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions[..., : k.shape[1]] if cache is None
                           else positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        # decode: append the new token's K/V at position cache_len
        assert s == 1
        with scope("attn/kv_write"):
            k_cache, v_cache = cache["k"], cache["v"]     # (B, KV, Smax, Dh)
            pos = cache_len                                # (B,) int32
            if cfg.kv_update == "dus":
                # per-example dynamic_update_slice — a true scatter; avoids
                # the one_hot broadcast that forces SPMD full
                # rematerialization of the seq-sharded cache (see
                # EXPERIMENTS.md §Perf cell B)
                def _upd(c, n, p):
                    return lax.dynamic_update_slice(c, n, (0, p, 0))
                k_cache = jax.vmap(_upd)(k_cache, k.transpose(0, 2, 1, 3), pos)
                v_cache = jax.vmap(_upd)(v_cache, v.transpose(0, 2, 1, 3), pos)
            else:
                oh = jax.nn.one_hot(pos, k_cache.shape[2], dtype=k.dtype)
                k_cache = (k_cache
                           + oh[:, None, :, None] * k.transpose(0, 2, 1, 3))
                v_cache = (v_cache
                           + oh[:, None, :, None] * v.transpose(0, 2, 1, 3))
            new_cache = {"k": k_cache, "v": v_cache}
        smax = k_cache.shape[2]
        with scope("attn/core"):
            if (cfg.decode_attn == "flashdecode" and dist is not None
                    and dist.model_size > 1 and smax % dist.model_size == 0):
                # flash-decoding: the cache stays SEQ-sharded end to end.
                # q is tiny (B,1,H,Dh) — replicate it; scores are S-sharded;
                # softmax over the sharded axis lowers to partial-max/sum
                # psums of (B,H,1) scalars instead of gathering the cache
                # (the measured 1 GiB/layer/step pathology; §Perf cell B).
                q_r = lax.with_sharding_constraint(
                    q, jax.sharding.NamedSharding(
                        dist.mesh, jax.sharding.PartitionSpec(
                            dist.bspec, None, None, None)))
                kc = dist.constrain_kv(k_cache)            # (B, KV, S, Dh)
                vc = dist.constrain_kv(v_cache)
                scale = 1.0 / math.sqrt(Dh)
                scores = jnp.einsum(
                    "bqhd,bhsd->bhqs", q_r,
                    jnp.repeat(kc, H // KV, axis=1),
                    preferred_element_type=jnp.float32) * scale
                scores = dist.constrain_scores(scores)     # (B, H, 1, S)@model
                valid = (jnp.arange(smax)[None, None, None, :]
                         < (cache_len + 1)[:, None, None, None])
                scores = jnp.where(valid, scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("bhqs,bhsd->bqhd",
                                 probs.astype(q.dtype),
                                 jnp.repeat(vc, H // KV, axis=1),
                                 preferred_element_type=jnp.float32
                                 ).astype(q.dtype)
            else:
                out = _decode_attention(q, k_cache, v_cache, cache_len + 1)
    else:
        with scope("attn/core"):
            k = _repeat_kv(k, H // KV)
            v = _repeat_kv(v, H // KV)
            if (dist is not None and cfg.attn_seq_shard and not is_cross
                    and s % max(dist.model_size, 1) == 0):
                # context parallelism: scores (B, H, S/TP, S) per device —
                # the remedy when heads cannot split the model axis
                q = dist.constrain_seq(q)
            chunk = cfg.attn_chunk or (1024 if s > 8192 else 0)
            if chunk and not is_cross and s % chunk == 0:
                out = _chunked_attention(q, k, v, causal=True,
                                         q_chunk=chunk, kv_chunk=chunk)
            else:
                out = _plain_attention(q, k, v, causal=not is_cross)
            if dist is not None and cfg.attn_seq_shard and not is_cross:
                out = dist.constrain_seq(out)
    with scope("attn/out"):
        out = out.reshape(b, s, H * Dh)
        return out @ cast(params["wo"], cdt), new_cache


# --------------------------------------------------------------------------
# FFNs
# --------------------------------------------------------------------------

def ffn_init(key, cfg: ModelConfig) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "w_gate": dense_init(ks[0], (D, F), dtype=dt),
        "w_up": dense_init(ks[1], (D, F), dtype=dt),
        "w_down": dense_init(ks[2], (F, D), scale=1.0 / math.sqrt(F), dtype=dt),
    }


def ffn_apply(params: Params, cfg: ModelConfig, x):
    cdt = _dtype(cfg)
    g = x @ cast(params["w_gate"], cdt)
    u = x @ cast(params["w_up"], cdt)
    return (jax.nn.silu(g) * u) @ cast(params["w_down"], cdt)


def cmix_init(key, cfg: ModelConfig) -> Params:
    """RWKV channel-mix: receptance-gated squared-relu FFN with token shift."""
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "cm_r": dense_init(ks[0], (D, D), dtype=dt),
        "cm_k": dense_init(ks[1], (D, F), dtype=dt),
        "cm_v": dense_init(ks[2], (F, D), scale=1.0 / math.sqrt(F), dtype=dt),
        "mix_k": jnp.full((D,), 0.5, dt),
        "mix_r": jnp.full((D,), 0.5, dt),
    }


def cmix_apply(params: Params, cfg: ModelConfig, x, x_prev=None):
    """x: (B,S,D). x_prev: (B,D) decode-state token shift; returns (y, last_x)."""
    cdt = _dtype(cfg)
    if x_prev is None:
        shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    else:
        shifted = x_prev[:, None, :]  # S == 1 decode
    xk = x * params["mix_k"].astype(cdt) + shifted * (1 - params["mix_k"].astype(cdt))
    xr = x * params["mix_r"].astype(cdt) + shifted * (1 - params["mix_r"].astype(cdt))
    r = jax.nn.sigmoid(xr @ params["cm_r"].astype(cdt))
    k = jnp.square(jax.nn.relu(xk @ params["cm_k"].astype(cdt)))
    return r * (k @ params["cm_v"].astype(cdt)), x[:, -1, :]


# --------------------------------------------------------------------------
# MoE (GShard-style capacity dispatch, expert-parallel friendly)
# --------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    E = cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "router": dense_init(ks[0], (D, E), scale=0.02, dtype=dt),
        "w_gate": dense_init(ks[1], (E, D, F), scale=1.0 / math.sqrt(D), dtype=dt),
        "w_up": dense_init(ks[2], (E, D, F), scale=1.0 / math.sqrt(D), dtype=dt),
        "w_down": dense_init(ks[3], (E, F, D), scale=1.0 / math.sqrt(F), dtype=dt),
    }


def moe_capacity(cfg: ModelConfig, group_tokens: int) -> int:
    moe = cfg.moe
    c = math.ceil(group_tokens * moe.top_k * moe.capacity_factor / moe.n_experts)
    return max(c, 1)


def moe_apply(params: Params, cfg: ModelConfig, x):
    """x: (B, S, D) -> (y, aux_loss).

    Tokens are grouped along the existing (B, S) layout: groups are rows of
    the batch when S > 1 (so dispatch never crosses the data-parallel axis),
    or groups of adjacent batch rows for decode shapes (S == 1). The dispatch
    is sort-free: positions within an expert come from a cumsum over the
    one-hot assignment; tokens past capacity are dropped (GShard semantics,
    capacity_factor 1.25).
    """
    moe = cfg.moe
    E, K = moe.n_experts, moe.top_k
    cdt = _dtype(cfg)
    b, s, d = x.shape
    if s > 1:
        groups, gtok = b, s
        xg = x
    else:
        gsz = min(b, 16)
        groups, gtok = b // gsz, gsz
        xg = x.reshape(groups, gtok, d)
    C = moe_capacity(cfg, gtok)

    with scope("moe/route"):
        logits = (xg @ cast(params["router"], cdt)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                  # (G,T,E)
        top_p, top_e = lax.top_k(probs, K)                       # (G,T,K)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        # position of each (token, k) inside its expert queue
        onehot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)     # (G,T,K,E)
        flat = onehot.reshape(groups, gtok * K, E)
        pos = jnp.cumsum(flat, axis=1) - flat                    # (G,T*K,E)
        pos = jnp.einsum("gte,gte->gt", pos, flat).reshape(groups, gtok, K)
        keep = pos < C
        pos = pos.astype(jnp.int32)

    with scope("moe/dispatch"):
        # scatter token indices into (G, E, C) dispatch table
        tok_ids = jnp.broadcast_to(jnp.arange(gtok)[None, :, None],
                                   top_e.shape)
        # gtok == OOB sentinel
        dispatch = jnp.full((groups, E, C), gtok, jnp.int32)
        gidx = jnp.broadcast_to(jnp.arange(groups)[:, None, None],
                                top_e.shape)
        dispatch = dispatch.at[
            gidx.reshape(groups, -1),
            jnp.where(keep, top_e, 0).reshape(groups, -1),
            jnp.where(keep, pos, C - 1).reshape(groups, -1),
        ].set(jnp.where(keep, tok_ids, gtok).reshape(groups, -1), mode="drop")

        # gather expert inputs (OOB sentinel -> zeros via fill)
        xpad = jnp.concatenate([xg, jnp.zeros((groups, 1, d), xg.dtype)],
                               axis=1)
        expert_in = jnp.take_along_axis(
            xpad[:, None], dispatch[..., None].clip(0, gtok), axis=2
        )  # (G, E, C, D)

    with scope("moe/experts"):
        h_g = jnp.einsum("gecd,edf->gecf", expert_in,
                         cast(params["w_gate"], cdt))
        h_u = jnp.einsum("gecd,edf->gecf", expert_in,
                         cast(params["w_up"], cdt))
        h = jax.nn.silu(h_g) * h_u
        expert_out = jnp.einsum("gecf,efd->gecd", h,
                                cast(params["w_down"], cdt))

    with scope("moe/combine"):
        # weight each dispatched slot and scatter-add back to tokens. slot
        # weights mirror the dispatch scatter; the OOB sentinel token id
        # (== gtok) lands in the padding row and is dropped by the final slice.
        slot_w = jnp.zeros((groups, E, C), jnp.float32)
        slot_w = slot_w.at[
            gidx.reshape(groups, -1),
            jnp.where(keep, top_e, 0).reshape(groups, -1),
            jnp.where(keep, pos, C - 1).reshape(groups, -1),
        ].add(jnp.where(keep, top_p, 0.0).reshape(groups, -1), mode="drop")
        weighted = (expert_out.astype(jnp.float32)
                    * slot_w[..., None]).reshape(groups, E * C, d)
        g_rows = jnp.broadcast_to(jnp.arange(groups)[:, None], (groups, E * C))
        out = jnp.zeros((groups, gtok + 1, d), jnp.float32)
        out = out.at[g_rows, dispatch.reshape(groups, -1)].add(
            weighted, mode="drop")
        y = out[:, :gtok].astype(cdt)

    with scope("moe/route"):
        # load-balancing auxiliary loss (Switch-style)
        me = probs.mean(axis=(0, 1))                       # (E,)
        ce = onehot.sum(axis=2).mean(axis=(0, 1))     # fraction routed per e
        aux = E * jnp.sum(me * ce / K)
    if s == 1:
        y = y.reshape(b, s, d)
    return y, aux


def moe_apply_ep(params: Params, cfg: ModelConfig, x, dist):
    """Expert-parallel MoE via shard_map (cfg.moe_shard == 'ep_a2a').

    Activations are replicated over the model axis (they are dp-sharded
    only), so every shard already holds every token: shard m builds the
    capacity dispatch for ITS E/TP experts only — dispatch tensors are
    TP-times smaller than the GSPMD dense-dispatch path — runs its expert
    FFNs locally, and the per-shard partial outputs combine with ONE
    (B, S, D) psum per layer. No token all_to_all is needed at all in
    this layout; the wire cost collapses to the dense-FFN pattern
    (EXPERIMENTS.md §Perf cell C3).
    """
    moe = cfg.moe
    E, K = moe.n_experts, moe.top_k
    TP = dist.model_size
    if TP <= 1 or E % TP != 0:
        return moe_apply(params, cfg, x)
    E_loc = E // TP
    cdt = _dtype(cfg)
    from jax.sharding import PartitionSpec as P  # local import (no cycle)

    def f(router, wg, wu, wd, xx):
        # router (D, E) replicated; wg/wu (E_loc, D, F), wd (E_loc, F, D)
        # local expert shards; xx (B_loc, S, D) replicated over 'model'.
        idx = lax.axis_index("model")
        b, s, d = xx.shape
        gtok = b * s
        xg = xx.reshape(1, gtok, d)
        with scope("moe/route"):
            logits = (xg @ cast(router, cdt)).astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)                # (1,T,E)
            top_p, top_e = lax.top_k(probs, K)
            top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

            # positions within each GLOBAL expert queue (identical math on
            # every shard — routing is deterministic), then keep only the
            # local expert range
            onehot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)
            flat = onehot.reshape(1, gtok * K, E)
            pos = jnp.cumsum(flat, axis=1) - flat
            pos = jnp.einsum("gte,gte->gt", pos, flat).reshape(1, gtok, K)
            C = moe_capacity(cfg, gtok)
            local = (top_e >= idx * E_loc) & (top_e < (idx + 1) * E_loc)
            keep = (pos < C) & local
            e_loc = jnp.where(local, top_e - idx * E_loc, 0)
            pos = pos.astype(jnp.int32)

        with scope("moe/dispatch"):
            tok_ids = jnp.broadcast_to(jnp.arange(gtok)[None, :, None],
                                       top_e.shape)
            dispatch = jnp.full((1, E_loc, C), gtok, jnp.int32)
            gidx = jnp.zeros_like(top_e)
            dispatch = dispatch.at[
                gidx.reshape(1, -1),
                jnp.where(keep, e_loc, 0).reshape(1, -1),
                jnp.where(keep, pos, C - 1).reshape(1, -1),
            ].set(jnp.where(keep, tok_ids, gtok).reshape(1, -1), mode="drop")

            xpad = jnp.concatenate([xg, jnp.zeros((1, 1, d), xg.dtype)],
                                   axis=1)
            expert_in = jnp.take_along_axis(
                xpad[:, None], dispatch[..., None].clip(0, gtok), axis=2)
        with scope("moe/experts"):
            h_g = jnp.einsum("gecd,edf->gecf", expert_in, cast(wg, cdt))
            h_u = jnp.einsum("gecd,edf->gecf", expert_in, cast(wu, cdt))
            h = jax.nn.silu(h_g) * h_u
            expert_out = jnp.einsum("gecf,efd->gecd", h, cast(wd, cdt))

        with scope("moe/combine"):
            slot_w = jnp.zeros((1, E_loc, C), jnp.float32)
            slot_w = slot_w.at[
                gidx.reshape(1, -1),
                jnp.where(keep, e_loc, 0).reshape(1, -1),
                jnp.where(keep, pos, C - 1).reshape(1, -1),
            ].add(jnp.where(keep, top_p, 0.0).reshape(1, -1), mode="drop")
            weighted = (expert_out.astype(jnp.float32)
                        * slot_w[..., None]).reshape(1, E_loc * C, d)
            g_rows = jnp.zeros((1, E_loc * C), jnp.int32)
            out = jnp.zeros((1, gtok + 1, d), jnp.float32)
            out = out.at[g_rows, dispatch.reshape(1, -1)].add(weighted,
                                                              mode="drop")
            y = lax.psum(out[:, :gtok], "model")   # combine partial outputs
        with scope("moe/route"):
            # aux loss: every shard sees all routing info — no comm needed
            me = probs.mean(axis=(0, 1))
            ce = onehot.sum(axis=2).mean(axis=(0, 1))
            aux = E * jnp.sum(me * ce / K)
        return y.reshape(b, s, d).astype(cdt), aux

    bspec = dist.bspec
    return jax.shard_map(
        f, mesh=dist.mesh,
        in_specs=(P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"],
      params["w_down"], x)


# --------------------------------------------------------------------------
# Mamba (selective state space)
# --------------------------------------------------------------------------

def mamba_init(key, cfg: ModelConfig) -> Params:
    mc = cfg.mamba or MambaConfig()
    D = cfg.d_model
    d_in = mc.expand * D
    dt_rank = mc.dt_rank or -(-D // 16)
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    A = jnp.broadcast_to(jnp.arange(1, mc.d_state + 1, dtype=jnp.float32),
                         (d_in, mc.d_state))
    return {
        "in_proj": dense_init(ks[0], (D, 2 * d_in), dtype=dt),
        "conv_w": dense_init(ks[1], (mc.d_conv, d_in), scale=0.5, dtype=dt),
        "conv_b": jnp.zeros((d_in,), dt),
        "x_proj": dense_init(ks[2], (d_in, dt_rank + 2 * mc.d_state), dtype=dt),
        "dt_proj": dense_init(ks[3], (dt_rank, d_in), dtype=dt),
        "dt_bias": jnp.full((d_in,), -4.6, dt),   # softplus^-1(0.01)
        "A_log": jnp.log(A).astype(dt),
        "D": jnp.ones((d_in,), dt),
        "out_proj": dense_init(ks[4], (d_in, D), dtype=dt),
    }


def _selective_scan(u, dt, B, Cm, A, chunk: int = 64):
    """u: (b, S, d_in); dt: (b, S, d_in); B, Cm: (b, S, N); A: (d_in, N).

    h_t = exp(A*dt_t) h_{t-1} + dt_t * B_t * u_t;  y_t = <Cm_t, h_t>.
    Chunked: sequential lax.scan over chunks, parallel associative scan inside.
    """
    b, S, d_in = u.shape
    N = A.shape[1]
    pad = (-S) % chunk
    if pad:
        u, dt, B, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                        for a in (u, dt, B, Cm))
    Sp = S + pad
    nc = Sp // chunk
    u = u.reshape(b, nc, chunk, d_in)
    dt = dt.reshape(b, nc, chunk, d_in)
    B = B.reshape(b, nc, chunk, N)
    Cm = Cm.reshape(b, nc, chunk, N)

    def chunk_step(h, inp):
        uc, dtc, Bc, Cc = inp  # (b, chunk, ...)
        dA = jnp.exp(dtc[..., None] * A[None, None].astype(jnp.float32))  # (b,c,d,N)
        dBu = (dtc * uc)[..., None] * Bc[..., None, :]                    # (b,c,d,N)

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        aa, bb = lax.associative_scan(combine, (dA, dBu), axis=1)
        h_seq = aa * h[:, None] + bb                                      # (b,c,d,N)
        y = jnp.einsum("bcdn,bcn->bcd", h_seq, Cc.astype(jnp.float32))
        return h_seq[:, -1], y

    h0 = jnp.zeros((b, d_in, N), jnp.float32)
    _, ys = lax.scan(chunk_step, h0,
                     (u.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2, 3),
                      B.transpose(1, 0, 2, 3), Cm.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3).reshape(b, Sp, d_in)
    return y[:, :S]


def mamba_apply(params: Params, cfg: ModelConfig, x, *, state=None):
    """x: (B, S, D). state: {conv: (B, d_conv-1, d_in), h: (B, d_in, N)} for
    decode (S == 1). Returns (y, new_state)."""
    mc = cfg.mamba or MambaConfig()
    cdt = _dtype(cfg)
    b, s, D = x.shape
    d_in = mc.expand * D
    xz = x @ params["in_proj"].astype(cdt)
    xi, z = jnp.split(xz, 2, axis=-1)                  # (B,S,d_in) each

    conv_w = params["conv_w"].astype(cdt)              # (d_conv, d_in)
    new_state = None
    if state is None:
        xpad = jnp.pad(xi, ((0, 0), (mc.d_conv - 1, 0), (0, 0)))
        conv = sum(xpad[:, i:i + s] * conv_w[i] for i in range(mc.d_conv))
    else:
        hist = jnp.concatenate([state["conv"], xi], axis=1)  # (B, d_conv, d_in)
        conv = jnp.einsum("bcd,cd->bd", hist, conv_w)[:, None]
        new_conv = hist[:, 1:]
    conv = jax.nn.silu(conv + params["conv_b"].astype(cdt))

    proj = conv @ params["x_proj"].astype(cdt)
    dt_rank = params["dt_proj"].shape[0]
    dt_x, Bm, Cm = jnp.split(proj, [dt_rank, dt_rank + mc.d_state], axis=-1)
    dt = jax.nn.softplus((dt_x @ params["dt_proj"].astype(cdt)).astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    if state is None:
        y = _selective_scan(conv.astype(jnp.float32), dt,
                            Bm.astype(jnp.float32), Cm.astype(jnp.float32), A)
    else:
        h = state["h"]
        dA = jnp.exp(dt[:, 0, :, None] * A[None])                   # (B,d,N)
        dBu = (dt[:, 0] * conv[:, 0].astype(jnp.float32))[..., None] \
            * Bm[:, 0, None, :].astype(jnp.float32)
        h = h * dA + dBu
        y = jnp.einsum("bdn,bn->bd", h, Cm[:, 0].astype(jnp.float32))[:, None]
        new_state = {"conv": new_conv, "h": h}
    y = y + conv.astype(jnp.float32) * params["D"].astype(jnp.float32)
    y = y.astype(cdt) * jax.nn.silu(z)
    return y @ params["out_proj"].astype(cdt), new_state


def mamba_state_init(cfg: ModelConfig, batch: int):
    mc = cfg.mamba or MambaConfig()
    d_in = mc.expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, mc.d_conv - 1, d_in), _dtype(cfg)),
        "h": jnp.zeros((batch, d_in, mc.d_state), jnp.float32),
    }


# --------------------------------------------------------------------------
# RWKV6 (Finch) time mix
# --------------------------------------------------------------------------

def rwkv6_init(key, cfg: ModelConfig) -> Params:
    rc = cfg.rwkv or RWKVConfig()
    D = cfg.d_model
    H = D // rc.head_size
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wr": dense_init(ks[0], (D, D), dtype=dt),
        "wk": dense_init(ks[1], (D, D), dtype=dt),
        "wv": dense_init(ks[2], (D, D), dtype=dt),
        "wg": dense_init(ks[3], (D, D), dtype=dt),
        "wo": dense_init(ks[4], (D, D), dtype=dt),
        "w0": jnp.full((D,), -2.0, dt),            # base decay (w = exp(-exp(.)))
        "w_a": dense_init(ks[5], (D, rc.decay_lora), dtype=dt),
        "w_b": dense_init(ks[6], (rc.decay_lora, D), scale=0.1, dtype=dt),
        "u": dense_init(ks[7], (H, rc.head_size), scale=0.5, dtype=dt),
        "mix_x": jnp.full((D,), 0.5, dt),
    }


def _wkv6_scan(r, k, v, w, u):
    """Linear recurrence with data-dependent per-channel decay (exact oracle).

    r,k,v: (B,S,H,n); w: (B,S,H,n) decay in (0,1); u: (H,n) bonus.
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
        o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)
    Sequential lax.scan over time — numerically exact for any decay strength.
    The chunked-parallel form (the performance path) lives in kernels/rwkv6
    and is validated against this oracle.
    """
    b, S, h, n = r.shape

    def step(state, inp):
        rt, kt, vt, wt = inp                       # (b, h, n) each
        kv = jnp.einsum("bhn,bhm->bhnm", kt, vt)
        o = jnp.einsum("bhn,bhnm->bhm", rt, state + u[None, :, :, None] * kv)
        state = state * wt[..., None] + kv
        return state, o

    state0 = jnp.zeros((b, h, n, n), jnp.float32)
    xs = tuple(a.transpose(1, 0, 2, 3).astype(jnp.float32) for a in (r, k, v, w))
    _, os_ = lax.scan(step, state0, xs)
    return os_.transpose(1, 0, 2, 3)               # (B, S, H, n)


def rwkv6_apply(params: Params, cfg: ModelConfig, x, *, state=None):
    """x: (B,S,D). state: {"S": (B,H,n,n), "x_prev": (B,D)} for decode."""
    rc = cfg.rwkv or RWKVConfig()
    cdt = _dtype(cfg)
    b, s, D = x.shape
    n = rc.head_size
    H = D // n

    if state is None:
        shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    else:
        shifted = state["x_prev"][:, None, :]
    mix = params["mix_x"].astype(cdt)
    xm = x * mix + shifted * (1 - mix)

    r = (xm @ params["wr"].astype(cdt)).reshape(b, s, H, n)
    k = (xm @ params["wk"].astype(cdt)).reshape(b, s, H, n)
    v = (xm @ params["wv"].astype(cdt)).reshape(b, s, H, n)
    g = jax.nn.silu(xm @ params["wg"].astype(cdt))
    w_log = params["w0"].astype(jnp.float32) + (
        jnp.tanh(xm @ params["w_a"].astype(cdt)) @ params["w_b"].astype(cdt)
    ).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(w_log)).reshape(b, s, H, n)   # decay in (0,1)
    u = params["u"].astype(jnp.float32)

    new_state = None
    if state is None:
        o = _wkv6_scan(r, k, v, w, u)
    else:
        S0 = state["S"]                                # (B,H,n,n)
        rf, kf, vf, wf = (a[:, 0].astype(jnp.float32) for a in (r, k, v, w))
        kv = jnp.einsum("bhn,bhm->bhnm", kf, vf)
        o = jnp.einsum("bhn,bhnm->bhm", rf, S0 + u[None, :, :, None] * kv)[:, None]
        S_new = S0 * wf[..., None] + kv
        new_state = {"S": S_new, "x_prev": x[:, -1, :]}
    o = o.reshape(b, s, D).astype(cdt) * g
    return o @ params["wo"].astype(cdt), new_state


def rwkv6_state_init(cfg: ModelConfig, batch: int):
    rc = cfg.rwkv or RWKVConfig()
    H = cfg.d_model // rc.head_size
    return {
        "S": jnp.zeros((batch, H, rc.head_size, rc.head_size), jnp.float32),
        "x_prev": jnp.zeros((batch, cfg.d_model), _dtype(cfg)),
    }
