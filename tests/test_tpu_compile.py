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
def granite_programs(one_chip):
    """granite-moe-1b-a400m's serving step at 128 slots of 256, compiled
    for the described chip from f32 parameter specs: (config, the served
    step's optimized HLO text, its unprepared core's)."""
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
    step = make_decode_fn(cfg)
    served, core = (fn(params, state, tok, pos).compile().as_text()
                    for fn in (step.lower, step.core.lower))
    return cfg, served, core


@pytest.fixture(scope="module")
def granite_step(granite_programs):
    """The served step: (config, optimized HLO text)."""
    cfg, served, _ = granite_programs
    return cfg, served


def test_granite_decode_step_scopes(granite_programs):
    """granite-moe-1b-a400m's serving step at 128 slots of 256, as the
    chip's compiler leaves its unprepared core handed f32 weights: XLA
    hoists each weight's f32 -> bf16 cast out of the layer loop without
    its metadata, and ``op_scopes`` still reads the expert weights' casts
    as ``cast``; every matmul and every sublayer has its scope."""
    import re

    from repro.analysis import hlo as H
    from repro.models import blocks

    _, _, text = granite_programs
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


def _weight_passes(text):
    """The passes over a whole weight of ``blocks.CAST_LEAVES`` in the
    entry computation: each conversion, copy or transpose (alone or as a
    fusion's root) that reads one, directly or through another such pass,
    and writes as many elements, as (name, shape)."""
    import re

    from repro.analysis import hlo as H
    from repro.models import blocks

    comps = H._split_computations(text)
    entry = next(c for c in comps.values() if c.is_entry)
    ops = {i.name: i for i in entry.instrs}
    passes = {"convert", "copy", "copy-start", "copy-done", "transpose",
              "bitcast"}

    def size(i):
        dims = re.search(r"\[([\d,]*)\]", i.shape).group(1)
        return math.prod(int(d) for d in dims.split(",") if d)

    def kind(i):
        m = re.search(r"calls=%?([\w.\-]+)", i.line)
        if i.op != "fusion" or not m:
            return i.op
        return next(j.op for j in comps[m.group(1)].instrs
                    if j.line.lstrip().startswith("ROOT"))

    def weight(i):
        """Whether ``i`` is a whole weight, or a pass over one."""
        if i.op == "parameter":
            m = re.search(r'op_name="params[^"]*\[\\?\'(\w+)\\?\'\]"',
                          i.line)
            return m is not None and m.group(1) in blocks.CAST_LEAVES
        src = ops.get(i.operands[0]) if i.operands else None
        return (kind(i) in passes and src is not None
                and size(i) == size(src) and weight(src))

    return [(i.name, i.shape) for i in entry.instrs
            if kind(i) in passes - {"bitcast", "copy-done"}
            and weight(i)]


def test_granite_served_step_casts_no_weight(granite_programs):
    """The served step is handed its weights in bf16 (``make_decode_fn``
    -> ``lm.serving_params``), so the chip's compiler leaves no op that
    ``op_scopes`` reads as ``cast``, no conversion of a whole weight and
    no copy or transpose of one; the core handed f32 weights has them
    (the nine converts and the weight prefetches), so the check sees
    what it looks for. Every sublayer keeps its scope."""
    from repro.analysis import hlo as H
    from repro.models import blocks

    _, served, core = granite_programs
    scopes = H.op_scopes(served)
    assert [n for n, sc in scopes.items() if sc == blocks.CAST] == []
    assert _weight_passes(served) == []
    assert set(blocks.SCOPES) <= set(scopes.values())
    found = _weight_passes(core)
    assert sum(n.startswith("convert") for n, _ in found) == 9
    assert any(n.startswith("copy-start") for n, _ in found)
    assert blocks.CAST in H.op_scopes(core).values()
