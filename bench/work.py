"""Operations and bytes that the algorithm needs, from a configuration's
sizes alone.

The yardstick of every roofline and utilization metric. It reads the
configuration file of the benchmark (``bench/configs/<name>.json``), never
the program, so a change to the program cannot move it. Nothing here depends
on how a step is implemented: a cast of f32 parameters to bf16 on every step,
or attention over dead KV slots, is work the algorithm does not need and is
not counted.

``param_counts`` is the arithmetic of ``repro.configs.base.ModelConfig.
param_counts`` for the decoder LMs the benchmark runs (attention mixer,
dense or MoE SwiGLU FFN), copied so that later changes to the program leave
it where it is.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def padded_vocab(c: dict) -> int:
    m = c["vocab_pad_multiple"]
    return -(-c["vocab_size"] // m) * m


def d_head(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def param_counts(c: dict) -> dict[str, float]:
    """Parameters of the whole model and those one token is multiplied by.

    ``embed`` and ``head`` are the two vocabulary tables (``head`` is 0 when
    they are tied); ``layers`` is every parameter of the layer stack;
    ``active`` counts the attention, the router, the experts a token is sent
    to and the output head, as the program's own count does."""
    D, H, KV = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    Dh, F, L = d_head(c), c["intermediate_size"], c["num_hidden_layers"]
    V = padded_vocab(c)
    embed = V * D
    head = 0 if c["tie_word_embeddings"] else V * D
    attn = D * H * Dh + 2 * D * KV * Dh + H * Dh * D
    norms = 2 * D
    E = c.get("num_local_experts", 0)
    if E:
        K = c["num_experts_per_tok"]
        ffn_total = E * 3 * D * F + D * E
        ffn_active = K * 3 * D * F + D * E
    else:
        ffn_total = ffn_active = 3 * D * F
    layers = L * (attn + norms + ffn_total)
    active = L * (attn + norms + ffn_active) + D + V * D
    return {"embed": float(embed), "head": float(head),
            "layers": float(layers),
            "total": float(embed + head + layers + D),
            "active": float(active)}


def kv_bytes_per_position(c: dict) -> int:
    """Keys and values of one position over all layers, at the KV dtype."""
    itemsize = {"bfloat16": 2, "float32": 4, "int8": 1}[c["kv_cache_dtype"]]
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"] * d_head(c)
            * itemsize)


def decode_step_work(c: dict, slots: int, live: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one serving step of ``slots`` tokens whose
    sequences hold ``live`` positions in all, the new ones included.

    FLOPs: 2 per active parameter per token, and attention over the live
    positions. Bytes: every parameter of the layers and the output head
    read once at the compute dtype, the embedding rows of the step's
    tokens, the live KV positions read, one new position written per token,
    and the f32 logits written."""
    pc = param_counts(c)
    flops = slots * 2.0 * pc["active"] + \
        4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * d_head(c) * live
    head = pc["head"] or pc["embed"]
    byt = ((pc["layers"] + head + c["hidden_size"]) * BF16
           + slots * c["hidden_size"] * BF16
           + live * kv_bytes_per_position(c)
           + slots * kv_bytes_per_position(c)
           + slots * c["vocab_size"] * F32)
    return flops, byt


def train_step_flops(c: dict, global_batch: int, seq_len: int) -> float:
    """Forward and backward FLOPs of one training step: 6 per active
    parameter per token (the embedding lookup multiplies nothing; the
    output head does) and causal attention, 3 times its
    forward 2 * S^2 * H * Dh per layer per sequence. Recomputation is not
    work the algorithm needs and is not counted."""
    tokens = global_batch * seq_len
    attn = (3 * 2.0 * seq_len * seq_len * c["num_attention_heads"]
            * d_head(c) * c["num_hidden_layers"] * global_batch)
    return 6.0 * param_counts(c)["active"] * tokens + attn
