"""Per-architecture smoke tests (assignment requirement): every arch's
REDUCED config runs one forward/train step and one decode step on CPU,
asserting shapes and finiteness. Plus semantic checks: prefill-vs-decode
equivalence, MoE dispatch vs dense oracle, decode-state mechanics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.models import blocks as B
from repro.models import lm


def tiny_batch(cfg, bsz=2, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, size=(bsz, seq + 1))
    batch = {"labels": jnp.asarray(toks[:, 1:], jnp.int32)}
    if cfg.frontend == "frames":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(bsz, seq, cfg.d_model)), jnp.float32)
    else:
        batch["tokens"] = jnp.asarray(toks[:, :-1], jnp.int32)
    if cfg.n_ctx_tokens:
        batch["ctx"] = jnp.asarray(
            rng.normal(size=(bsz, cfg.n_ctx_tokens, cfg.d_model)),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_train(arch):
    cfg = get_config(arch).reduced()
    params = lm.init(cfg, jax.random.key(0))
    batch = tiny_batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, cfg, batch), has_aux=True)(params)
    assert jnp.isfinite(loss), arch
    assert loss.shape == ()
    gn = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, arch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_decode(arch):
    cfg = get_config(arch).reduced()
    params = lm.init(cfg, jax.random.key(0))
    bsz, cache = 2, 8
    state = lm.decode_state_init(cfg, bsz, cache)
    batch = ({"frames": jnp.ones((bsz, 1, cfg.d_model), jnp.float32)}
             if cfg.frontend == "frames" else
             {"tokens": jnp.zeros((bsz, 1), jnp.int32)})
    logits, ns = lm.decode_step(params, cfg, state, batch,
                                jnp.zeros((bsz,), jnp.int32))
    assert logits.shape == (bsz, cfg.vocab_size), arch
    assert bool(jnp.isfinite(logits).all()), arch
    # state must actually change (cache write happened)
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(ns)))
    assert changed, arch


@pytest.mark.parametrize("arch", ["llama3-8b", "rwkv6-3b", "musicgen-large",
                                  "jamba-1.5-large-398b"])
def test_prefill_decode_equivalence(arch):
    """Teacher-forcing the same tokens through decode steps must match the
    parallel forward's final logits (KV-cache correctness)."""
    # f32 compute: the test checks ALGORITHMIC equivalence, not bf16 drift
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              compute_dtype="float32")
    if cfg.moe is not None:
        # ample capacity: token dropping differs between prefill grouping
        # (per sequence) and decode grouping (across batch) by design
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init(cfg, jax.random.key(1))
    bsz, seq = 2, 8
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, size=(bsz, seq), dtype=np.int32)

    if cfg.frontend == "frames":
        emb = np.asarray(params["embed"], np.float32)
        full = {"frames": jnp.asarray(emb[toks])}
        stepb = lambda i: {"frames": jnp.asarray(emb[toks[:, i:i + 1]])}  # noqa: E731
    else:
        full = {"tokens": jnp.asarray(toks)}
        stepb = lambda i: {"tokens": jnp.asarray(toks[:, i:i + 1])}  # noqa: E731
    logits_par = lm.prefill(params, cfg, full)

    state = lm.decode_state_init(cfg, bsz, seq)
    logits_seq = None
    for i in range(seq):
        logits_seq, state = lm.decode_step(params, cfg, state, stepb(i),
                                           jnp.full((bsz,), i, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_par),
                               np.asarray(logits_seq), rtol=1e-4,
                               atol=1e-4)


def test_kv_update_dus_matches_onehot():
    cfg = get_config("glm4-9b").reduced()
    cfg2 = dataclasses.replace(cfg, kv_update="dus")
    params = lm.init(cfg, jax.random.key(0))
    b = 2
    t = np.random.default_rng(0).integers(0, cfg.vocab_size, (b, 4),
                                          dtype=np.int32)

    def roll(c):
        st = lm.decode_state_init(c, b, 8)
        outs = []
        for i in range(4):
            lg, st = lm.decode_step(params, c, st,
                                    {"tokens": jnp.asarray(t[:, i:i + 1])},
                                    jnp.full((b,), i, jnp.int32))
            outs.append(lg)
        return np.asarray(jnp.stack(outs))

    np.testing.assert_allclose(roll(cfg), roll(cfg2), atol=1e-5)


def _decode_attention_oracle(q, k_cache, v_cache, kv_len):
    """Decode attention as the repeat path computes it: the cache moved to
    (B, Smax, KV, Dh), each KV head repeated to its query heads, plain
    attention over every position below ``kv_len``."""
    n_rep = q.shape[2] // k_cache.shape[1]
    k = B._repeat_kv(k_cache.transpose(0, 2, 1, 3), n_rep)
    v = B._repeat_kv(v_cache.transpose(0, 2, 1, 3), n_rep)
    return B._plain_attention(q, k, v, causal=False, kv_len=kv_len)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("group", [1, 2, 3, 16])
def test_decode_attention_matches_repeat_oracle(group, dtype, tol):
    """Grouped-query decode over the cache as stored == repeat + plain
    attention, for MHA (G=1) and GQA groups of 2, 3 and 16, with ragged
    valid lengths (one position, part of the cache, all of it)."""
    b, kv, smax, dh = 3, 2, 24, 16
    kq, kk, kvv = jax.random.split(jax.random.key(group), 3)
    q = jax.random.normal(kq, (b, 1, kv * group, dh)).astype(dtype)
    k_cache = jax.random.normal(kk, (b, kv, smax, dh)).astype(dtype)
    v_cache = jax.random.normal(kvv, (b, kv, smax, dh)).astype(dtype)
    kv_len = jnp.asarray([1, 13, smax], jnp.int32)
    got = B._decode_attention(q, k_cache, v_cache, kv_len)
    want = _decode_attention_oracle(q, k_cache, v_cache, kv_len)
    assert got.shape == want.shape == q.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_granite_greedy_decode_matches_repeat_oracle(monkeypatch):
    """granite-moe (reduced, G=2, bf16): a teacher-forced prompt, then
    greedy steps, give the same tokens as the same model served through
    the repeat oracle."""
    cfg = get_config("granite-moe-1b-a400m").reduced()
    params = lm.init(cfg, jax.random.key(0))
    bsz, prompt, steps = 2, 4, 4
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                             (bsz, prompt), dtype=np.int32)

    def greedy():
        # traced afresh on each call, with the attention in place then
        step = jax.jit(lambda st, tok, pos: lm.decode_step(
            params, cfg, st, {"tokens": tok}, pos))
        state = lm.decode_state_init(cfg, bsz, prompt + steps)
        out, nxt = [], None
        for i in range(prompt + steps):
            tok = toks[:, i:i + 1] if i < prompt else nxt
            logits, state = step(state, jnp.asarray(tok),
                                 jnp.full((bsz,), i, jnp.int32))
            nxt = np.asarray(jnp.argmax(logits, -1), np.int32)[:, None]
            if i >= prompt - 1:
                out.append(nxt[:, 0])
        return np.stack(out, 1)

    got = greedy()
    monkeypatch.setattr(B, "_decode_attention", _decode_attention_oracle)
    want = greedy()
    np.testing.assert_array_equal(got, want)


def test_chunked_attention_matches_plain():
    # f32 compute so the only difference is the summation algorithm
    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              compute_dtype="float32")
    params = lm.init(cfg, jax.random.key(0))
    batch = tiny_batch(cfg, bsz=2, seq=32)
    plain = lm.forward(params, cfg, batch)[0]
    cfgc = dataclasses.replace(cfg, attn_chunk=8)
    chunked = lm.forward(params, cfgc, batch)[0]
    np.testing.assert_allclose(np.asarray(plain, np.float32),
                               np.asarray(chunked, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_moe_matches_dense_oracle_when_capacity_ample():
    """With capacity_factor high enough that nothing drops, capacity
    dispatch == dense weighted mixture of expert outputs."""
    cfg = dataclasses.replace(
        get_config("granite-moe-1b-a400m").reduced(),
        moe=dataclasses.replace(
            get_config("granite-moe-1b-a400m").reduced().moe,
            capacity_factor=8.0),
    )
    params = B.moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                          jnp.float32).astype(jnp.dtype(cfg.compute_dtype))
    got, aux = B.moe_apply(params, cfg, x)

    # dense oracle: run every expert on every token, mix by top-k weights
    cdt = x.dtype
    logits = (x @ params["router"].astype(cdt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    h_g = jnp.einsum("bsd,edf->ebsf", x, params["w_gate"].astype(cdt))
    h_u = jnp.einsum("bsd,edf->ebsf", x, params["w_up"].astype(cdt))
    h = jax.nn.silu(h_g) * h_u
    eo = jnp.einsum("ebsf,efd->ebsd", h, params["w_down"].astype(cdt))
    oh = jax.nn.one_hot(top_e, cfg.moe.n_experts, dtype=jnp.float32)
    w = jnp.einsum("bske,bsk->ebs", oh, top_p)
    want = jnp.einsum("ebs,ebsd->bsd", w, eo.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)
    assert float(aux) > 0


def test_param_counts_sane():
    """Analytic counts land within 25% of actual leaf-count totals."""
    for arch in ("llama3-8b", "dbrx-132b", "rwkv6-3b"):
        cfg = get_config(arch)
        specs = lm.param_specs(cfg)
        actual = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(specs))
        analytic = cfg.param_counts()["total"]
        assert abs(actual - analytic) / actual < 0.25, \
            (arch, actual, analytic)


def test_long500k_applicability():
    ok, _ = shape_applicable(get_config("rwkv6-3b"), SHAPES["long_500k"])
    assert ok
    ok, why = shape_applicable(get_config("llama3-8b"), SHAPES["long_500k"])
    assert not ok and "full-attention" in why
    ok, _ = shape_applicable(get_config("jamba-1.5-large-398b"),
                             SHAPES["long_500k"])
    assert ok
