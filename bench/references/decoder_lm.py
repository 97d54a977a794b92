"""Plain reference of a decoder-only LM: embedding, pre-norm attention
(GQA, RoPE) and SwiGLU feed-forward (dense, or top-k routed experts with no
capacity limit), final RMSNorm, output head.

It imports nothing of the program. It reads a configuration file of the
benchmark (``bench/configs/<name>.json``) and works in float32 with every
matmul at HIGHEST precision, in straightforward ``jax.numpy``: no kernel,
no cache, no batching tricks, every expert computed for every token and
weighted by the router. ``precision="fp8"`` is the control: every matmul
operand is rounded to float8 e4m3 (one scale per tensor) first, the step
below the bf16 compute the configurations state.

The weights are made here, from the seed, in the pytree layout the program
consumes (``param_shapes``), so the program and the reference read the
same arrays and the reference takes nothing the program made.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(c: dict):
    D, H, KV = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    Dh = c.get("head_dim") or D // H
    m = c["vocab_pad_multiple"]
    Vp = -(-c["vocab_size"] // m) * m
    return D, H, KV, Dh, c["intermediate_size"], c["num_hidden_layers"], Vp


def seed_key(seed: int):
    """A key from any non-negative seed: ``jax.random.key`` keeps only the
    low 32 bits of a larger one, so the high bits are folded in."""
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


# --------------------------------------------------------------- weights

# The router's logits have a standard deviation of about this many units:
# routing as decisive as a trained router's. At the program's own init
# (0.02, a deviation of 0.64 over 32 experts) routing is all but uniform,
# a bf16 rounding flips one of a token's experts in most layers, and bf16
# serving lies as far from the float32 reference as float8 does.
ROUTER_LOGIT_STD = 4.0


def param_shapes(c: dict) -> dict:
    """{path: (shape, init scale)}; a scale of None is a norm gain,
    drawn as 1 + 0.05 * normal so that a norm applied without its gain
    shows. The scales are the program's own init but for the router."""
    D, H, KV, Dh, F, L, Vp = dims(c)
    out = {
        ("embed",): ((Vp, D), 0.02),
        ("final_norm",): ((D,), None),
        ("blocks", 0, "norm1"): ((L, D), None),
        ("blocks", 0, "norm2"): ((L, D), None),
        ("blocks", 0, "mixer", "wq"): ((L, D, H * Dh), D ** -0.5),
        ("blocks", 0, "mixer", "wk"): ((L, D, KV * Dh), D ** -0.5),
        ("blocks", 0, "mixer", "wv"): ((L, D, KV * Dh), D ** -0.5),
        ("blocks", 0, "mixer", "wo"): ((L, H * Dh, D), (H * Dh) ** -0.5),
    }
    if not c["tie_word_embeddings"]:
        out[("head",)] = ((Vp, D), 0.02)
    E = c.get("num_local_experts", 0)
    if E:
        out[("blocks", 0, "ffn", "router")] = ((L, D, E),
                                               ROUTER_LOGIT_STD * D ** -0.5)
        out[("blocks", 0, "ffn", "w_gate")] = ((L, E, D, F), D ** -0.5)
        out[("blocks", 0, "ffn", "w_up")] = ((L, E, D, F), D ** -0.5)
        out[("blocks", 0, "ffn", "w_down")] = ((L, E, F, D), F ** -0.5)
    else:
        out[("blocks", 0, "ffn", "w_gate")] = ((L, D, F), D ** -0.5)
        out[("blocks", 0, "ffn", "w_up")] = ((L, D, F), D ** -0.5)
        out[("blocks", 0, "ffn", "w_down")] = ((L, F, D), F ** -0.5)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    tree["blocks"] = (tree["blocks"][0],)
    return tree


def make_params(c: dict, key) -> dict:
    """All weights from the key (``seed_key(seed)``), in float32 (the
    dtype the program stores and serves them in). Call under ``jax.jit`` so
    they are made on the device in one program."""
    shapes = param_shapes(c)
    keys = jax.random.split(key, len(shapes))
    flat = {}
    for k, (path, (shape, scale)) in zip(keys, sorted(shapes.items(),
                                                      key=str)):
        z = jax.random.normal(k, shape, jnp.float32)
        flat[path] = 1.0 + 0.05 * z if scale is None else z * scale
    return _nest(flat)


# ------------------------------------------------------------ the model

def _round8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _q8(x):
    """A matmul operand in float8, forward and backward: the cotangent is
    rounded with a scale of its own, as float8 training scales its
    gradients."""
    return _round8(x)


_q8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _mm(eq, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x: (B, S, heads, Dh); rotate the two halves of each head by the
    position's angles."""
    S, Dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(c, p, h, fp8):
    D, H, KV, Dh, *_ = dims(c)
    B, S, _ = h.shape
    q = rope(_mm("bsd,de->bse", h, p["wq"], fp8).reshape(B, S, H, Dh),
             c["rope_theta"])
    k = rope(_mm("bsd,de->bse", h, p["wk"], fp8).reshape(B, S, KV, Dh),
             c["rope_theta"])
    v = _mm("bsd,de->bse", h, p["wv"], fp8).reshape(B, S, KV, Dh)
    k = jnp.repeat(k, H // KV, axis=2)   # query head j reads kv head j // g
    v = jnp.repeat(v, H // KV, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, fp8) / math.sqrt(Dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, fp8)
    return _mm("bse,ed->bsd", a.reshape(B, S, H * Dh), p["wo"], fp8)


def feed_forward(c, p, h, fp8):
    E = c.get("num_local_experts", 0)
    if not E:
        g = _mm("bsd,df->bsf", h, p["w_gate"], fp8)
        u = _mm("bsd,df->bsf", h, p["w_up"], fp8)
        return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"], fp8)
    K = c["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm("bsd,de->bse", h, p["router"], fp8), -1)
    top_p, top_e = lax.top_k(probs, K)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_e, E) * top_p[..., None], axis=-2)
    g = _mm("bsd,edf->bsef", h, p["w_gate"], fp8)
    u = _mm("bsd,edf->bsef", h, p["w_up"], fp8)
    hidden = jax.nn.silu(g) * u * weight[..., None]
    return _mm("bsef,efd->bsd", hidden, p["w_down"], fp8)


def hidden_states(c, params, tokens, fp8=False, remat=False):
    """Final-normed hidden states (B, S, D) of a causal pass over
    ``tokens`` (B, S)."""
    eps = c["rms_norm_eps"]
    x = params["embed"][tokens]

    def block(x, p):
        x = x + attention(c, p["mixer"], rmsnorm(x, p["norm1"], eps), fp8)
        return x + feed_forward(c, p["ffn"], rmsnorm(x, p["norm2"], eps),
                                fp8), None

    if remat:
        block = jax.checkpoint(block)
    with jax.default_matmul_precision("highest"):
        x, _ = lax.scan(block, x, params["blocks"][0])
    return rmsnorm(x, params["final_norm"], eps)


def head_logits(c, params, x, fp8=False):
    head = params.get("head", params["embed"])
    logits = _mm("bsd,vd->bsv", x, head, fp8)
    return logits[..., : c["vocab_size"]]


@partial(jax.jit, static_argnums=(0, 4, 5))
def served_logits(c, params, tokens, first, n, fp8=False):
    """Logits (B, n, vocab) at positions first .. first + n - 1."""
    x = hidden_states(c, params, tokens, fp8)
    return head_logits(c, params, lax.dynamic_slice_in_dim(x, first, n, 1),
                       fp8)


# -------------------------------------------------------------- training

def _loss_sum(c, params, tokens, labels, fp8):
    x = hidden_states(c, params, tokens, fp8, remat=True)
    logits = head_logits(c, params, x, fp8)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll)


@partial(jax.jit, static_argnums=(0, 4))
def _grad_sum(c, params, tokens, labels, fp8):
    return jax.value_and_grad(partial(_loss_sum, c))(params, tokens, labels,
                                                      fp8)


def loss_and_grad(c, params, tokens, labels, *, rows_per_call: int,
                  fp8=False, shard=None):
    """Mean token cross-entropy over the batch and its gradient, summed
    over blocks of ``rows_per_call`` rows so that it fits. ``shard`` places
    each block (a sharding over the batch rows)."""
    total, grads = 0.0, None
    for r in range(0, tokens.shape[0], rows_per_call):
        t, l = tokens[r:r + rows_per_call], labels[r:r + rows_per_call]
        if shard is not None:
            t, l = jax.device_put((t, l), shard)
        s, g = _grad_sum(c, params, t, l, fp8)
        total = total + s
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = tokens.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


def lr_at(o: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    warm = min(1.0, (step + 1) / max(o["warmup_steps"], 1))
    prog = min(max((step - o["warmup_steps"])
                   / max(o["decay_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


@partial(jax.jit, static_argnums=(0,))
def _adamw(o_items, params, grads, mu, nu, step, lr):
    """AdamW with the gradient clipped to a global norm: decoupled weight
    decay on every stored leaf of two or more dimensions."""
    o = dict(o_items)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gn, 1e-9)),
        grads)
    mu = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, nu,
                      grads)
    c = step + 1.0
    bc1, bc2 = 1 - o["b1"] ** c, 1 - o["b2"] ** c

    def upd(p, m, v):
        u = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
        if p.ndim >= 2:
            u = u + o["weight_decay"] * p
        return p - lr * u

    return jax.tree.map(upd, params, mu, nu), mu, nu


def train_steps(c, opt: dict, params, batches, *, rows_per_call: int,
                fp8=False, shard=None, grad_rows=None, grad_scale=1.0):
    """Take one AdamW step per batch from ``params``. Returns the loss of
    each step, the gradient the optimizer got at the first step (before
    clipping) and the parameters after the last.

    ``grad_rows`` and ``grad_scale`` plant the faults a data-parallel step
    can have: the gradient (and loss) of a slice of the rows only, scaled.
    """
    o_items = tuple(sorted(opt.items()))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for i, (tokens, labels) in enumerate(batches):
        if grad_rows is not None:
            tokens, labels = tokens[grad_rows], labels[grad_rows]
        loss, grads = loss_and_grad(c, params, tokens, labels,
                                    rows_per_call=rows_per_call, fp8=fp8,
                                    shard=shard)
        grads = jax.tree.map(lambda g: g * grad_scale, grads)
        if first_grad is None:
            first_grad = grads
        losses.append(float(loss))
        params, mu, nu = _adamw(o_items, params, grads, mu, nu,
                                jnp.float32(i), jnp.float32(lr_at(opt, i)))
    return losses, first_grad, params
