"""The work counts against hand arithmetic at a reduced configuration."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import work  # noqa: E402

# D=32, 4 heads of 8, 2 KV heads, F=64, 2 layers, vocab 128 (pad 16)
SMALL = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=8, intermediate_size=64,
             vocab_size=120, vocab_pad_multiple=16, kv_cache_dtype="bfloat16")


def test_param_counts_dense_tied():
    c = dict(SMALL, tie_word_embeddings=True)
    attn = 32 * 32 + 2 * 32 * 16 + 32 * 32          # q, k, v, o
    ffn = 3 * 32 * 64
    layer = attn + 2 * 32 + ffn                     # + two norm gains
    pc = work.param_counts(c)
    assert pc["embed"] == 128 * 32 and pc["head"] == 0
    assert pc["layers"] == 2 * layer
    assert pc["total"] == 128 * 32 + 2 * layer + 32
    assert pc["active"] == 2 * layer + 32 + 128 * 32


def test_param_counts_moe_untied():
    c = dict(SMALL, tie_word_embeddings=False, num_local_experts=4,
             num_experts_per_tok=2)
    attn = 32 * 32 + 2 * 32 * 16 + 32 * 32
    total_ffn = 4 * 3 * 32 * 64 + 32 * 4            # experts + router
    active_ffn = 2 * 3 * 32 * 64 + 32 * 4
    pc = work.param_counts(c)
    assert pc["head"] == 128 * 32
    assert pc["layers"] == 2 * (attn + 64 + total_ffn)
    assert pc["active"] == 2 * (attn + 64 + active_ffn) + 32 + 128 * 32


def test_decode_step_work():
    c = dict(SMALL, tie_word_embeddings=False, num_local_experts=4,
             num_experts_per_tok=2)
    pc = work.param_counts(c)
    slots, live = 3, 3 * 5
    flops, byt = work.decode_step_work(c, slots, live)
    kv_pos = 2 * 2 * 2 * 8 * 2          # k and v, layers, kv heads, Dh, bf16
    assert work.kv_bytes_per_position(c) == kv_pos
    assert flops == slots * 2 * pc["active"] + 4 * 2 * 4 * 8 * live
    assert byt == ((pc["layers"] + pc["head"] + 32) * 2 + slots * 32 * 2
                   + live * kv_pos + slots * kv_pos + slots * 120 * 4)


def test_train_step_flops():
    c = dict(SMALL, tie_word_embeddings=True)
    pc = work.param_counts(c)
    gb, s = 2, 16
    attn = 3 * 2 * s * s * 4 * 8 * 2 * gb            # causal, fwd + bwd
    assert work.train_step_flops(c, gb, s) == 6 * pc["active"] * gb * s + attn
