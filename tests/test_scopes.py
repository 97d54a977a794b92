"""Named scopes of the serving step (``repro.models.blocks.SCOPES``), the
op -> scope map of its compiled program (``repro.analysis.hlo.op_scopes``)
on the CPU at a reduced MoE size, and the host spans of
``repro.launch.serve.serve_batch``."""
import ast
import contextlib
import dataclasses
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo as H
from repro.configs import get_config
from repro.launch import serve
from repro.models import blocks as B
from repro.models import lm

REPO = Path(__file__).resolve().parents[1]
KNOWN = B.SCOPES + (B.CAST,)


def _cfg():
    cfg = get_config("granite-moe-1b-a400m").reduced()
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=2.0))


def _step_hlo(cfg) -> str:
    """The optimized HLO of the serving step: 32 slots of 16 positions,
    two decode groups of 16 tokens."""
    params = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    state = lm.decode_state_specs(cfg, 32, 16)
    tok = jax.ShapeDtypeStruct((32, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((32,), jnp.int32)
    return serve.make_decode_fn(cfg).lower(
        params, state, tok, pos).compile().as_text()


@pytest.fixture(scope="module")
def step():
    text = _step_hlo(_cfg())
    return text, H.op_scopes(text)


def _loop_body(text):
    comps = H._split_computations(text)
    bodies = {re.search(r"body=%?([\w.\-]+)", i.line).group(1)
              for c in comps.values() for i in c.instrs if i.op == "while"}
    assert bodies
    return [i for b in bodies for i in comps[b].instrs]


def test_every_op_of_the_layer_loop_has_a_scope(step):
    text, scopes = step
    checked = [i for i in _loop_body(text)
               if i.op in ("fusion", "dot", "convert", "scatter")
               and not i.shape.split("{")[0].endswith("[]")]  # the counter
    assert len(checked) >= 10
    assert {i.name: scopes[i.name] for i in checked
            if scopes[i.name] not in KNOWN} == {}
    # attention and MoE both show, down to their sublayers
    seen = {scopes[i.name] for i in checked}
    assert {"attn/qkv", "attn/kv_write", "attn/core", "moe/route",
            "moe/experts", "moe/combine"} <= seen


def test_the_expert_weight_casts_read_as_cast(step):
    text, scopes = step
    comps = H._split_computations(text)
    caller = {}                      # fused computation -> its fusion
    for c in comps.values():
        for i in c.instrs:
            m = re.search(r"calls=%?([\w.\-]+)", i.line)
            if i.op == "fusion" and m:
                caller[m.group(1)] = (c.name, i.name)
    where = {i.name: c.name for c in comps.values() for i in c.instrs}
    casts = [i.name for c in comps.values() for i in c.instrs
             if 'moe/experts/cast/convert_element_type"' in i.line]
    assert len(casts) == 3           # w_gate, w_up, w_down
    for name in casts:
        while where[name] in caller:  # up to the op a trace shows
            name = caller[where[name]][1]
        assert scopes[name] == B.CAST, name


def test_scopes_change_only_metadata(step, monkeypatch):
    """With every scope a no-op, the program is the same once its source
    metadata is stripped."""
    text, _ = step
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _step_hlo(_cfg())
    assert 'moe/experts' not in plain
    assert H.strip_metadata(plain) == H.strip_metadata(text)
    assert "metadata=" not in H.strip_metadata(text)


@pytest.mark.parametrize("op_name, want", [
    ("jit(f)/while/body/closed_call/attn/qkv/dot_general", "attn/qkv"),
    ("jit(f)/while/body/closed_call/moe/experts/cast/convert_element_type",
     "cast"),
    ("moe/route/reduce_sum", "moe/route"),
    ("jit(f)/head/mul", "head"),
    ("jit(f)/while/body/dynamic_slice", None),
    # the last segment is the primitive, never a scope
    ("jit(f)/embed", None),
    ("attn/kv_write", None),
])
def test_scope_of_an_op_name(op_name, want):
    assert H.scope_of(op_name, B.SCOPES, B.CAST) == want


# an entry computation as XLA leaves it for a TPU: a weight's conversion
# hoisted out of the layer loop keeps no metadata, nor do the copies XLA
# adds; the KV state is a parameter too, but not a weight
HOISTED = """
HloModule m

%fused_computation.1 (p0: bf16[8,16], p1: bf16[16,16]) -> bf16[8,16] {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %p1 = bf16[16,16]{1,0} parameter(1)
  ROOT %convolution.1 = bf16[8,16]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(decode_fn)/while/body/closed_call/moe/experts/gecd,edf->gecf/dot_general"}
}

%fused_computation.2 (p0.1: bf16[8,16]) -> (f32[8,16], bf16[8,16]) {
  %p0.1 = bf16[8,16]{1,0} parameter(0)
  %add.1 = bf16[8,16]{1,0} add(%p0.1, %p0.1), metadata={op_name="jit(decode_fn)/while/body/closed_call/attn/kv_write/add"}
  %mul.1 = bf16[8,16]{1,0} multiply(%add.1, %add.1), metadata={op_name="jit(decode_fn)/while/body/closed_call/attn/kv_write/mul"}
  %convert.9 = f32[8,16]{1,0} convert(%mul.1)
  %neg.1 = bf16[8,16]{1,0} negate(%p0.1), metadata={op_name="jit(decode_fn)/while/body/closed_call/attn/core/neg"}
  ROOT %tuple.1 = (f32[8,16]{1,0}, bf16[8,16]{1,0}) tuple(%convert.9, %neg.1)
}

ENTRY %main (params__w_up__.1: f32[16,16], params__wo__.1: f32[16,16], state_0___kv__.1: bf16[8,16]) -> bf16[8,16] {
  %params__w_up__.1 = f32[16,16]{1,0} parameter(0)
  %params__wo__.1 = f32[16,16]{1,0} parameter(1)
  %state_0___kv__.1 = bf16[8,16]{1,0} parameter(2)
  %convert.82 = bf16[16,16]{1,0} convert(%params__w_up__.1)
  %copy-start.2 = (f32[16,16]{1,0}, f32[16,16]{1,0}, u32[]) copy-start(%params__wo__.1)
  %copy-done.2 = f32[16,16]{1,0} copy-done(%copy-start.2)
  %convert.86 = bf16[16,16]{1,0} convert(%copy-done.2)
  %convert.90 = f32[8,16]{1,0} convert(%state_0___kv__.1)
  %fusion.7 = bf16[8,16]{1,0} fusion(%state_0___kv__.1, %convert.82), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(decode_fn)/while/body/closed_call/moe/experts/gecd,edf->gecf/dot_general"}
  %copy.5 = bf16[8,16]{1,0} copy(%fusion.7)
  %multi_fusion.3 = (f32[8,16]{1,0}, bf16[8,16]{1,0}) fusion(%copy.5), kind=kLoop, calls=%fused_computation.2
  ROOT %add.9 = bf16[8,16]{1,0} add(%copy.5, %convert.86)
}
"""


@pytest.mark.parametrize("op, want", [
    ("convert.82", "cast"),            # a weight's hoisted cast
    ("convert.86", "cast"),            # ... of its prefetched copy
    ("convert.90", "unscoped"),        # the state is no weight
    ("fusion.7", "moe/experts"),       # the fusion's own op_name
    ("convolution.1", "moe/experts"),
    ("copy.5", "moe/experts"),         # a layout copy: its producer's
    ("multi_fusion.3", "attn/kv_write"),  # most of its fused ops
    ("convert.9", "attn/kv_write"),
    ("add.9", "unscoped"),             # fed by two scopes
    ("params__w_up__.1", "unscoped"),
])
def test_op_scopes_where_xla_left_no_scope(op, want):
    assert H.op_scopes(HOISTED)[op] == want


def test_scope_names_are_checked():
    assert "attn/kv_write" in B.SCOPES and len(set(B.SCOPES)) == 10
    with pytest.raises(ValueError):
        B.scope("attention")


def test_serve_batch_spans_are_the_benchmark_drivers():
    """The operator's entry names its spans as the benchmark's serving
    driver does, so that a profile of either names idle gaps alike."""
    tree = ast.parse((REPO / "bench/drivers/serve_static.py").read_text())
    spans = [ast.literal_eval(v) for d in ast.walk(tree)
             if isinstance(d, ast.Dict)
             for k, v in zip(d.keys, d.values)
             if isinstance(k, ast.Constant) and k.value == "spans"]
    assert spans == [serve.SPANS]


def test_serve_batch_emits_its_spans(monkeypatch):
    names = []

    class Recorder(contextlib.nullcontext):
        def __init__(self, name, **kw):
            names.append(name)
            super().__init__()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    cfg = get_config("smollm-135m").reduced()
    serve.serve_batch(cfg, batch=2, prompt_len=3, gen=2, quiet=True)
    admit, prefill, decode, read = serve.SPANS
    assert names == [admit, prefill] + [read, decode] * 2
