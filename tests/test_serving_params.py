"""The serving step's weights held in the compute dtype
(``repro.launch.serve.make_decode_fn`` -> ``DecodeStep``,
``repro.models.lm.serving_params``): which leaves are cast, that the
served numbers are the unprepared step's, when a copy is made, reused,
replaced or not made at all, and what ``.lower`` lowers. On the CPU, at
reduced sizes."""
import contextlib
import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex

from repro.configs import ARCHS, get_config
from repro.launch import serve
from repro.models import blocks as B
from repro.models import lm


def _setup(cfg, batch=2, cache=8, seed=0):
    params = lm.init(cfg, jax.random.key(seed))
    return params, lm.decode_state_init(cfg, batch, cache)


def _args(batch=2, tok=0, pos=0):
    return (jnp.full((batch, 1), tok, jnp.int32),
            jnp.full((batch,), pos, jnp.int32))


def _granite():
    return get_config("granite-moe-1b-a400m").reduced()


@pytest.mark.parametrize("arch", ARCHS)
def test_prepared_leaves_are_cast_or_the_callers_own(arch):
    cfg = get_config(arch).reduced()
    cdt = jnp.dtype(cfg.compute_dtype)
    params, _ = _setup(cfg)
    prepared = lm.serving_params(cfg, params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    got = jax.tree.leaves(prepared)
    assert len(got) == len(flat)
    cast = 0
    for (path, w), p in zip(flat, got):
        if path[-1].key in B.CAST_LEAVES:
            assert p.dtype == cdt, path
            np.testing.assert_array_equal(np.asarray(p),
                                          np.asarray(w.astype(cdt)))
            cast += 1
        else:
            assert p is w, path        # a norm gain is never rounded
    assert cast and cast < len(flat)


def _ops(jaxpr, out):
    """(primitive, name stack) of every equation, into sub-jaxprs."""
    for e in jaxpr.eqns:
        out.append((e.primitive.name, str(e.source_info.name_stack)))
        for v in e.params.values():
            for j in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(j, jex.ClosedJaxpr):
                    _ops(j.jaxpr, out)
                elif isinstance(j, jex.Jaxpr):
                    _ops(j, out)
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_served_step_traces_no_cast(arch):
    """Every ``blocks.cast`` of the step reads a leaf of
    ``CAST_LEAVES``: handed the prepared weights, the step traces no op
    in a ``cast`` scope, where handed the stored ones it traces some."""
    cfg = get_config(arch).reduced()
    params, state = _setup(cfg)
    step = serve.make_decode_fn(cfg)

    def casts(p):
        jaxpr = jax.make_jaxpr(step.core)(p, state, *_args())
        return [o for o in _ops(jaxpr.jaxpr, [])
                if B.CAST in o[1].split("/")]

    assert casts(params)
    assert casts(lm.serving_params(cfg, params)) == []


@pytest.mark.parametrize("arch", ARCHS)
def test_greedy_decode_matches_the_unprepared_step(arch):
    cfg = get_config(arch).reduced()
    params, state = _setup(cfg)
    step = serve.make_decode_fn(cfg)
    runs = []
    for fn in (step, step.core):
        st, tok, toks, logits = state, np.zeros((2, 1), np.int32), [], []
        for i in range(4):
            lg, st = fn(params, st, jnp.asarray(tok),
                        jnp.full((2,), i, jnp.int32))
            tok = np.asarray(jnp.argmax(lg, -1), np.int32)[:, None]
            toks.append(tok)
            logits.append(np.asarray(lg))
        runs.append((np.concatenate(toks, 1), np.stack(logits)))
    (tok_a, lg_a), (tok_b, lg_b) = runs
    np.testing.assert_array_equal(tok_a, tok_b)
    np.testing.assert_allclose(lg_a, lg_b, rtol=1e-5, atol=1e-5)
    assert step.weights_prepared == 1 and step.weights_reused == 3


@pytest.mark.parametrize("over", [
    {"compute_dtype": "float32", "kv_cache_dtype": "float32"},
    {"param_dtype": "bfloat16"},
], ids=["f32-twin", "stored-bf16"])
def test_a_config_in_its_compute_dtype_holds_no_copy(over):
    cfg = dataclasses.replace(_granite(), **over)
    params, state = _setup(cfg)
    assert lm.serving_params(cfg, params) is params
    step = serve.make_decode_fn(cfg)
    assert step.weights(params) is params
    want, _ = step.core(params, state, *_args())
    for _ in range(2):
        got, _ = step(params, state, *_args())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (step.weights_prepared, step.weights_reused) == (0, 0)
    assert step._held is None


def test_the_copy_is_made_once_and_reused():
    cfg = _granite()
    params, state = _setup(cfg)
    step = serve.make_decode_fn(cfg)
    step(params, state, *_args())
    first = step.weights(params)
    step(params, state, *_args())
    assert step.weights(params) is first
    assert (step.weights_prepared, step.weights_reused) == (1, 3)
    # another dict over the same arrays is the same weights
    step(dict(params), state, *_args())
    assert (step.weights_prepared, step.weights_reused) == (1, 4)


def test_new_leaves_replace_the_held_copy():
    """A new params object, or one leaf swapped, makes a new copy and
    drops the old one: one copy is held at a time."""
    cfg = _granite()
    params, state = _setup(cfg)
    step = serve.make_decode_fn(cfg)
    step(params, state, *_args())
    step(params, state, *_args())
    old = weakref.ref(next(w for w in jax.tree.leaves(step.weights(params))
                           if w.dtype == jnp.bfloat16))
    fresh, _ = _setup(cfg, seed=1)
    step(fresh, state, *_args())
    assert (step.weights_prepared, step.weights_reused) == (2, 2)
    gc.collect()
    assert old() is None
    swapped = dict(fresh, final_norm=fresh["final_norm"] * 2)
    got, _ = step(swapped, state, *_args())
    want, _ = step.core(swapped, state, *_args())
    assert step.weights_prepared == 3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    step(swapped, state, *_args())
    assert (step.weights_prepared, step.weights_reused) == (3, 3)


def test_a_call_under_jit_caches_nothing():
    cfg = _granite()
    params, state = _setup(cfg)
    step = serve.make_decode_fn(cfg)
    got, _ = jax.jit(step)(params, state, *_args())
    assert (step.weights_prepared, step.weights_reused) == (0, 0)
    assert step._held is None
    want, _ = step(params, state, *_args())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_lower_lowers_what_a_call_runs():
    """``.lower`` on the stored specs lowers the core on the prepared
    weights' specs: the executable a call runs."""
    cfg = _granite()
    params, state = _setup(cfg)
    step = serve.make_decode_fn(cfg)
    specs = jax.eval_shape(lambda: params)
    lowered = step.lower(specs, state, *_args())
    want = step.core.lower(lm.serving_params(cfg, params), state, *_args())
    assert lowered.as_text() == want.as_text()
    assert lowered.as_text() != step.core.lower(
        specs, state, *_args()).as_text()
    exe = lowered.compile()
    got, _ = exe(step.weights(params), state, *_args())
    ref, _ = step.core(params, state, *_args())
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_the_copy_is_made_under_its_own_span(monkeypatch):
    names = []

    class Recorder(contextlib.nullcontext):
        def __init__(self, name, **kw):
            names.append(name)
            super().__init__()

    cfg = _granite()
    params, state = _setup(cfg)
    step = serve.make_decode_fn(cfg)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    for _ in range(3):
        step(params, state, *_args())
    assert names == ["prepare_weights"]
    assert "prepare_weights" not in serve.SPANS


def test_serve_batch_reports_its_weight_counters(capsys):
    out = serve.serve_batch(_granite(), batch=2, prompt_len=3, gen=2)
    # the warm-up call prepares; the 3 prefill and 2 decode steps reuse
    assert (out["weights_prepared"], out["weights_reused"]) == (1, 5)
    assert "weights prepared 1, reused 5" in capsys.readouterr().out
