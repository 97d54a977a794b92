"""The LM: embedding → pattern-scanned backbone → (tied) head, with train,
prefill and decode entry points.

Layer stacking: ``cfg.pattern`` (a tuple of BlockSpecs) is applied
``cfg.n_groups`` times via ``lax.scan`` over group-stacked parameters; the
pattern itself is a python-level loop (so heterogeneous interleaves like
Jamba's 1:7 mamba:attn carry no parameter padding). ``unroll=True`` replaces
the scan with a python loop — used by the HLO-analyzer validation tests
(XLA's cost_analysis counts while bodies once; see analysis/hlo.py).

Distribution is by sharding constraint (GSPMD); the vocab-parallel
embedding / cross-entropy use shard_map so that no vocab-sized all-gather is
ever materialized (see distributed/vocab_parallel.py).
"""
from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import BlockSpec, ModelConfig
from repro.models import blocks as B

Params = dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, blk: BlockSpec) -> Params:
    ks = iter(jax.random.split(key, 8))
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if blk.mixer in ("attn", "cross_attn"):
        p["mixer"] = B.attn_init(next(ks), cfg, cross=blk.mixer == "cross_attn")
    elif blk.mixer == "mamba":
        p["mixer"] = B.mamba_init(next(ks), cfg)
    elif blk.mixer == "rwkv6":
        p["mixer"] = B.rwkv6_init(next(ks), cfg)
    else:
        raise ValueError(blk.mixer)
    if blk.ffn != "none":
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        if blk.ffn == "dense":
            p["ffn"] = B.ffn_init(next(ks), cfg)
        elif blk.ffn == "moe":
            p["ffn"] = B.moe_init(next(ks), cfg)
        elif blk.ffn == "cmix":
            p["ffn"] = B.cmix_init(next(ks), cfg)
        else:
            raise ValueError(blk.ffn)
    return p


def init(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 3 + len(cfg.pattern))
    dt = jnp.dtype(cfg.param_dtype)
    Vp = cfg.padded_vocab

    def stack_init(k, blk):
        return jax.vmap(lambda kk: _block_init(kk, cfg, blk))(
            jax.random.split(k, cfg.n_groups))

    params: Params = {
        "embed": B.dense_init(keys[0], (Vp, cfg.d_model), scale=0.02, dtype=dt),
        "blocks": tuple(stack_init(keys[3 + i], blk)
                        for i, blk in enumerate(cfg.pattern)),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = B.dense_init(keys[1], (Vp, cfg.d_model),
                                      scale=0.02, dtype=dt)
    return params


def param_specs(cfg: ModelConfig) -> Params:
    """Allocation-free parameter ShapeDtypeStructs (for the dry-run)."""
    return jax.eval_shape(lambda: init(cfg, jax.random.key(0)))


# --------------------------------------------------------------------------
# decode-state init
# --------------------------------------------------------------------------

def _block_state_init(cfg: ModelConfig, blk: BlockSpec, batch: int,
                      cache_len: int) -> Params:
    kv_dt = jnp.dtype(cfg.kv_cache_dtype) if cfg.kv_cache_dtype != "int8" \
        else jnp.int8
    st: Params = {}
    if blk.mixer == "attn":
        KV, Dh = cfg.n_kv_heads, cfg.d_head
        st["kv"] = {
            "k": jnp.zeros((batch, KV, cache_len, Dh), kv_dt),
            "v": jnp.zeros((batch, KV, cache_len, Dh), kv_dt),
        }
        if cfg.kv_cache_dtype == "int8":
            st["kv"]["k_scale"] = jnp.zeros((batch, KV, cache_len), jnp.float32)
            st["kv"]["v_scale"] = jnp.zeros((batch, KV, cache_len), jnp.float32)
    elif blk.mixer == "cross_attn":
        KV, Dh = cfg.n_kv_heads, cfg.d_head
        st["kv"] = {
            "k": jnp.zeros((batch, KV, cfg.n_ctx_tokens, Dh),
                           jnp.dtype(cfg.compute_dtype)),
            "v": jnp.zeros((batch, KV, cfg.n_ctx_tokens, Dh),
                           jnp.dtype(cfg.compute_dtype)),
        }
    elif blk.mixer == "mamba":
        st["ssm"] = B.mamba_state_init(cfg, batch)
    elif blk.mixer == "rwkv6":
        st["ssm"] = B.rwkv6_state_init(cfg, batch)
    if blk.ffn == "cmix":
        st["cm_x_prev"] = jnp.zeros((batch, cfg.d_model),
                                    jnp.dtype(cfg.compute_dtype))
    return st


def decode_state_init(cfg: ModelConfig, batch: int, cache_len: int):
    """Stacked-over-groups decode state, one entry per pattern position."""
    def stack(blk):
        one = lambda: _block_state_init(cfg, blk, batch, cache_len)  # noqa: E731
        leaves = jax.eval_shape(one)
        return jax.tree.map(
            lambda s: jnp.zeros((cfg.n_groups,) + s.shape, s.dtype), leaves)
    return tuple(stack(blk) for blk in cfg.pattern)


def decode_state_specs(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.eval_shape(lambda: decode_state_init(cfg, batch, cache_len))


# --------------------------------------------------------------------------
# block application
# --------------------------------------------------------------------------

def _apply_block(bp: Params, cfg: ModelConfig, blk: BlockSpec, x, positions,
                 *, ctx=None, state=None, pos=None, train: bool = True,
                 dist=None):
    """Returns (x, new_state, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    with _norm_scope(blk.mixer in ("attn", "cross_attn"), "attn/qkv"):
        h = B.rmsnorm(x, bp["norm1"], cfg.norm_eps)
    new_state: Params = {}

    if blk.mixer in ("attn", "cross_attn"):
        is_cross = blk.mixer == "cross_attn"
        if state is not None and not train:
            kv = state["kv"]
            if is_cross:
                # static cross-attn cache: no update, attend over ctx tokens
                mix = B.attn_decode_readonly(bp["mixer"], cfg, h, kv)
                new_state["kv"] = kv
            else:
                mix, nkv = B.attn_apply(bp["mixer"], cfg, h, positions,
                                        cache=kv, cache_len=pos, dist=dist)
                new_state["kv"] = nkv
        else:
            mix, _ = B.attn_apply(bp["mixer"], cfg, h, positions,
                                  ctx=ctx if is_cross else None, dist=dist)
    elif blk.mixer == "mamba":
        mix, nst = B.mamba_apply(bp["mixer"], cfg, h,
                                 state=None if train else state["ssm"])
        if not train:
            new_state["ssm"] = nst
    elif blk.mixer == "rwkv6":
        mix, nst = B.rwkv6_apply(bp["mixer"], cfg, h,
                                 state=None if train else state["ssm"])
        if not train:
            new_state["ssm"] = nst
    else:
        raise ValueError(blk.mixer)

    if blk.parallel and blk.ffn != "none":
        # Cohere-style: attn and ffn both read the same normed input
        f, aux2, fstate = _apply_ffn(bp, cfg, blk, h, state, train,
                                     dist=dist)
        x = x + mix + f
    else:
        x = x + mix
        if blk.ffn != "none":
            with _norm_scope(blk.ffn == "moe", "moe/route"):
                h2 = B.rmsnorm(x, bp["norm2"], cfg.norm_eps)
            f, aux2, fstate = _apply_ffn(bp, cfg, blk, h2, state, train,
                                         dist=dist)
            x = x + f
        else:
            aux2, fstate = jnp.zeros((), jnp.float32), {}
    aux = aux + aux2
    new_state.update(fstate)
    return x, new_state, aux


def _norm_scope(scoped: bool, name: str):
    """A pre-norm belongs to the sublayer it feeds, where that has a scope."""
    return B.scope(name) if scoped else contextlib.nullcontext()


def _apply_ffn(bp, cfg, blk, h, state, train, dist=None):
    aux = jnp.zeros((), jnp.float32)
    fstate: Params = {}
    if blk.ffn == "dense":
        f = B.ffn_apply(bp["ffn"], cfg, h)
    elif blk.ffn == "moe":
        if cfg.moe_shard == "ep_a2a" and dist is not None:
            f, aux = B.moe_apply_ep(bp["ffn"], cfg, h, dist)
        else:
            f, aux = B.moe_apply(bp["ffn"], cfg, h)
    elif blk.ffn == "cmix":
        xp = None if train else state["cm_x_prev"]
        f, last = B.cmix_apply(bp["ffn"], cfg, h, x_prev=xp)
        if not train:
            fstate["cm_x_prev"] = last
    else:
        raise ValueError(blk.ffn)
    return f, aux, fstate


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------

def _embed_tokens(params, cfg: ModelConfig, batch, dist=None):
    cdt = jnp.dtype(cfg.compute_dtype)
    if cfg.frontend == "frames" and "frames" in batch:
        return batch["frames"].astype(cdt)
    tokens = batch["tokens"]
    with B.scope("embed"):
        if dist is not None and dist.vocab_parallel(cfg):
            return dist.vp_embed(params["embed"], tokens, cfg)
        return B.cast(params["embed"], cdt)[tokens]


def forward(params: Params, cfg: ModelConfig, batch, *, dist=None,
            unroll: bool = False):
    """Causal full-sequence forward. batch: {"tokens"|"frames", "ctx"?}.
    Returns (x_final (B,S,D), aux_loss)."""
    x = _embed_tokens(params, cfg, batch, dist)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    ctx = batch.get("ctx")
    if ctx is not None:
        ctx = ctx.astype(x.dtype)

    def apply_group(xc, gp):
        aux = jnp.zeros((), jnp.float32)
        if dist is not None:
            xc = dist.constrain_act(xc)
        for p, blk in enumerate(cfg.pattern):
            xc, _, a = _apply_block(gp[p], cfg, blk, xc, positions,
                                    ctx=ctx, train=True, dist=dist)
            aux = aux + a
        return xc, aux

    if cfg.remat == "block":
        apply_group = jax.checkpoint(
            apply_group,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif cfg.remat == "full":
        apply_group = jax.checkpoint(apply_group)

    if unroll:
        auxes = []
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda l: l[g], params["blocks"])
            x, a = apply_group(x, gp)
            auxes.append(a)
        aux = jnp.stack(auxes).sum() if auxes else jnp.zeros((), jnp.float32)
    else:
        x, auxes = lax.scan(lambda xc, gp: apply_group(xc, gp),
                            x, params["blocks"])
        aux = auxes.sum()

    x = B.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def lm_head(params: Params, cfg: ModelConfig):
    return params.get("head", params["embed"])


def loss_fn(params: Params, cfg: ModelConfig, batch, *, dist=None,
            unroll: bool = False):
    """Cross-entropy LM loss; labels masked where < 0."""
    x, aux = forward(params, cfg, batch, dist=dist, unroll=unroll)
    labels = batch["labels"]
    head = lm_head(params, cfg)
    if dist is not None and dist.vocab_parallel(cfg):
        ce = dist.vp_cross_entropy(head, x, labels, cfg)
    else:
        logits = (x @ head.astype(x.dtype).T).astype(jnp.float32)
        logits = logits[..., : cfg.vocab_size]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        ce = lse - ll
    mask = (labels >= 0).astype(jnp.float32)
    loss = (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux, "tokens": mask.sum()}


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def decode_step(params: Params, cfg: ModelConfig, state, batch, pos, *,
                dist=None):
    """One decode step. batch: {"tokens": (B,1)} | {"frames": (B,1,D)} (+ctx).
    state: from decode_state_init; pos: (B,) write/attend position.
    Returns (logits (B, vocab), new_state)."""
    x = _embed_tokens(params, cfg, batch, dist)
    b = x.shape[0]
    positions = pos[:, None]

    def group_step(xc, inp):
        gp, gs = inp
        if dist is not None:
            xc = dist.constrain_act(xc)
        new_gs = []
        for p, blk in enumerate(cfg.pattern):
            xc, nst, _ = _apply_block(gp[p], cfg, blk, xc, positions,
                                      state=gs[p], pos=pos, train=False,
                                      dist=dist)
            new_gs.append(nst)
        return xc, tuple(new_gs)

    x, new_state = lax.scan(group_step, x, (params["blocks"], state))
    with B.scope("head"):
        x = B.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = lm_head(params, cfg)
        if (cfg.decode_return == "token" and dist is not None
                and dist.vocab_parallel(cfg)):
            # greedy token id per row; the (B, V) logits never materialize
            token = dist.vp_greedy_token(head, x[:, 0], cfg)
            return token, new_state
        logits = (x[:, 0] @ B.cast(head, x.dtype).T).astype(jnp.float32)
        return logits[..., : cfg.vocab_size], new_state


def serving_params(cfg: ModelConfig, params: Params) -> Params:
    """``params`` as the serving step can hold them: each leaf of
    ``blocks.CAST_LEAVES`` in the compute dtype, cast once by one jitted
    program in a ``cast`` scope, under a ``prepare_weights`` host span;
    every other leaf is the caller's own array. Where no such leaf differs
    from the compute dtype, ``params`` itself is returned."""
    cdt = jnp.dtype(cfg.compute_dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [w for _, w in flat]
    picked = [i for i, (path, w) in enumerate(flat)
              if isinstance(path[-1], jax.tree_util.DictKey)
              and path[-1].key in B.CAST_LEAVES and w.dtype != cdt]
    if not picked:
        return params
    with jax.profiler.TraceAnnotation("prepare_weights"):
        cast = _cast_weights(cdt, [leaves[i] for i in picked])
    for i, w in zip(picked, cast):
        leaves[i] = w
    return treedef.unflatten(leaves)


@partial(jax.jit, static_argnums=0)
def _cast_weights(dtype, weights):
    return [B.cast(w, dtype) for w in weights]


def prefill(params: Params, cfg: ModelConfig, batch, *, dist=None):
    """Full-sequence prefill returning last-position logits.

    (Serving realism note: state materialization for the subsequent decode is
    exercised by decode_step from decode_state_init; the prefill benchmark
    shape measures the forward itself, which dominates.)"""
    x, _ = forward(params, cfg, batch, dist=dist)
    with B.scope("head"):
        head = lm_head(params, cfg)
        logits = (x[:, -1] @ B.cast(head, x.dtype).T).astype(jnp.float32)
        return logits[..., : cfg.vocab_size]
