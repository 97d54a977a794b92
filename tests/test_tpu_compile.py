"""Compile the Pallas kernels at real widths for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib lowers each kernel
through Mosaic for a ``v5e:2x2`` topology that is described, not attached,
and refuses what the chip would refuse (block shapes off the (8, 128)
tiling, primitives Mosaic cannot lower, too much VMEM) — all of which the
interpret-mode tests in test_kernels.py cannot see. The topology is built
in a module fixture, never at import, so every xdist worker collects the
same tests and only the worker that runs this file loads libtpu.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cellcopy.kernel import cellcopy
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.rwkv6.kernel import wkv6


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent
    compilation cache off: a TPU executable written here could not be
    read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # keep libtpu from writing log files outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel
    return compiled


@pytest.mark.parametrize("block_cells", [1, 8, 16])
def test_cellcopy_64k_cells_compiles(one_chip, block_cells):
    """Paper Fig 9's 64 KiB cells, 16 MiB message; the checksum layout
    compiles for any block_cells, including ones off the 8-row tiling."""
    _compile(lambda x: cellcopy(x, block_cells=block_cells,
                                interpret=False),
             one_chip, ((256, 64 * 1024 // 4), jnp.int32))


def test_flash_attention_glm4_heads_compiles(one_chip):
    """glm4-9b heads: 32 query heads over 2 KV heads of 128, S=4096."""
    b, h, kv, s, d = 1, 32, 2, 4096, 128
    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
             one_chip, ((b, h, s, d), jnp.bfloat16),
             ((b, kv, s, d), jnp.bfloat16), ((b, kv, s, d), jnp.bfloat16))


def test_wkv6_rwkv6_3b_heads_compiles(one_chip):
    """rwkv6-3b: 40 heads of 64, S=4096 in chunks of 32."""
    b, h, s, n = 1, 40, 4096, 64
    x = ((b, h, s, n), jnp.float32)
    _compile(lambda r, k, v, w, u: wkv6(r, k, v, w, u, interpret=False),
             one_chip, x, x, x, x, ((h, n), jnp.float32))


# granite-moe-1b-a400m served at 128 slots of 256 positions
GRANITE_SLOTS, GRANITE_SMAX = 128, 256


@pytest.fixture(scope="module")
def granite_step(one_chip):
    """granite-moe-1b-a400m's serving step at 128 slots of 256, compiled
    for the described chip: (config, optimized HLO text)."""
    import dataclasses

    from repro.configs import get_config
    from repro.launch.serve import make_decode_fn
    from repro.models import lm

    cfg = get_config("granite-moe-1b-a400m")
    cfg = dataclasses.replace(
        cfg, norm_eps=1e-6, tie_embeddings=True,
        moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0))))
    state = on_chip(lm.decode_state_specs(cfg, GRANITE_SLOTS, GRANITE_SMAX))
    tok = jax.ShapeDtypeStruct((GRANITE_SLOTS, 1), jnp.int32,
                               sharding=one_chip)
    pos = jax.ShapeDtypeStruct((GRANITE_SLOTS,), jnp.int32,
                               sharding=one_chip)
    text = make_decode_fn(cfg).lower(params, state, tok, pos).compile(
        ).as_text()
    return cfg, text


def test_granite_decode_step_scopes(granite_step):
    """granite-moe-1b-a400m's serving step at 128 slots of 256, as the
    chip's compiler leaves it: XLA hoists each weight's f32 -> bf16 cast
    out of the layer loop without its metadata, and ``op_scopes`` still
    reads the expert weights' casts as ``cast``; every matmul and every
    sublayer has its scope."""
    import re

    from repro.analysis import hlo as H
    from repro.models import blocks

    _, text = granite_step
    scopes = H.op_scopes(text)
    comps = H._split_computations(text)
    entry = next(c for c in comps.values() if c.is_entry)
    experts = [i.name for i in entry.instrs if i.op == "convert"
               and re.search(r"ffn____w_(gate|up|down)__", i.line)]
    assert len(experts) == 3
    assert {scopes[n] for n in experts} == {blocks.CAST}
    matmuls = [i.name for c in comps.values() for i in c.instrs
               if i.op == "fusion" and "convolution(" in "\n".join(
                   j.line for j in comps[re.search(
                       r"calls=%?([\w.\-]+)", i.line).group(1)].instrs)]
    assert matmuls
    assert {scopes[n] for n in matmuls} <= set(blocks.SCOPES)
    assert set(blocks.SCOPES) <= set(scopes.values())


def test_granite_decode_attention_reads_cache_in_place(granite_step):
    """Decode attention reads the bf16 cache as stored: no array that an
    op of ``attn/core`` or ``attn/kv_write`` writes to memory is an f32
    copy of a layer's cache, or the cache repeated to all 16 query heads
    (in any dtype or layout). Ops inside a fusion write nothing to
    memory, so only the ops of unfused computations are read."""
    import re

    from repro.analysis import hlo as H

    cfg, text = granite_step
    per_layer = GRANITE_SLOTS * GRANITE_SMAX * cfg.d_head
    cache = per_layer * cfg.n_kv_heads
    repeated = per_layer * cfg.n_heads
    assert cfg.n_heads > cfg.n_kv_heads        # GQA: a repeat is visible
    scopes = H.op_scopes(text)
    comps = H._split_computations(text)
    fused = {re.search(r"calls=%?([\w.\-]+)", i.line).group(1)
             for c in comps.values() for i in c.instrs if i.op == "fusion"}
    seen = 0
    for c in comps.values():
        if c.name in fused:
            continue
        for i in c.instrs:
            if scopes.get(i.name) not in ("attn/core", "attn/kv_write"):
                continue
            seen += 1
            for dt, dims in H._SHAPE_RE.findall(i.shape):
                n = math.prod(int(d) for d in dims.split(",") if d)
                assert not (dt == "f32" and n >= cache), (i.name, i.shape)
                assert n not in (repeated, cfg.n_layers * repeated), (
                    i.name, i.shape)
    assert seen
