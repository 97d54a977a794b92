"""Entry points: the serving driver's clocks and kept logits, the shared
compile-cache helper, and chip_smoke.py's refusal to run without a TPU
(its phases themselves are exercised here at tiny sizes on the CPU)."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch.serve import make_decode_fn, serve_batch
from repro.models import lm

ROOT = Path(__file__).resolve().parent.parent


def test_serve_batch_keeps_the_logits_each_token_came_from():
    cfg = get_config("granite-moe-1b-a400m").reduced()
    params = lm.init(cfg, jax.random.key(0))
    out = serve_batch(cfg, batch=2, prompt_len=4, gen=3, params=params,
                      keep_logits=2, quiet=True)
    assert out["tokens"].shape == (2, 3)
    assert len(out["logits"]) == 2
    for key in ("compile_s", "prefill_s", "decode_s"):
        assert out[key] > 0
    # greedy: each kept logits row picked the token that followed it
    for j, lg in enumerate(out["logits"]):
        assert np.array_equal(np.asarray(lg).argmax(-1), out["tokens"][:, j])
    # and they are the step's own logits, teacher-forced
    step = make_decode_fn(cfg)
    state = lm.decode_state_init(cfg, 2, 7)
    feed = np.concatenate([out["prompts"], out["tokens"][:, :1]], axis=1)
    ref = []
    for i in range(feed.shape[1]):
        lg, state = step(params, state, jnp.asarray(feed[:, i:i + 1]),
                         jnp.full((2,), i, jnp.int32))
        ref.append(np.asarray(lg))
    np.testing.assert_allclose(np.asarray(out["logits"][0]), ref[3],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out["logits"][1]), ref[4],
                               rtol=1e-5, atol=1e-5)


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_a_fixed_ignored_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_importing_entry_points_sets_no_cache():
    import importlib
    before = jax.config.jax_compilation_cache_dir
    for mod in ("repro.launch.serve", "repro.launch.train", "chip_smoke"):
        importlib.reload(importlib.import_module(mod))
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_the_cpu(monkeypatch, capsys):
    assert jax.devices()[0].platform == "cpu"
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    got = capsys.readouterr()
    assert "'cpu'" in got.err
    assert '"ok"' not in got.out


@pytest.mark.parametrize("phase", ["serve", "cellcopy", "flash", "wkv6"])
def test_chip_smoke_phases_at_tiny_size(phase):
    """The script's phases pass on the CPU (kernels interpreted) at tiny
    sizes: control flow and comparisons, not the chip."""
    if phase == "serve":
        chip_smoke.serve_phase(get_config("granite-moe-1b-a400m").reduced(),
                               batch=2, prompt_len=4, gen=4, check_steps=2,
                               seed=0)
    elif phase == "cellcopy":
        chip_smoke.cellcopy_phase(n_cells=16, cell_bytes=4096,
                                  block_cells=8, seed=0)
    elif phase == "flash":
        chip_smoke.flash_phase(b=1, h=4, kv=2, s=256, d=64, seed=0)
    else:
        chip_smoke.wkv6_phase(b=1, h=2, s=128, n=16, seed=0)
