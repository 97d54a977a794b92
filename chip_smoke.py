#!/usr/bin/env python3
"""Smoke run of the JAX path on one TPU, in one process.

  python chip_smoke.py               # one chip: serving + the three kernels
  python chip_smoke.py --four-chips  # four chips: data-parallel grad sync

One chip: ``repro.launch.serve.serve_batch`` serves a seeded batch with
granite-moe-1b-a400m at its published widths (24 layers, 32 experts top-8,
vocab 49155), and the chip's logits for the first generated tokens are
checked against the same jitted step run on the host CPU backend. Then
``cellcopy`` (64 KiB cells), ``flash_attention`` (glm4-9b heads) and
``wkv6`` (rwkv6-3b heads) each run once, compiled by Mosaic, against their
``ref.py``.

Four chips: ``distributed.schedules.make_cmpi_train_step`` trains
smollm-135m at its published widths for a few steps on a ("pod", "data") =
(2, 2) mesh, with ``sync_grads`` plain and int8, and is compared with the
same step on a flat ("data",) = (4,) mesh.

Every result line names the device it ran on. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed. Without a TPU the script exits non-zero before
any phase runs; it never falls back to the CPU. It starts no other
process: the chip belongs to this one.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Serving check: the chip's logits vs the same jitted step on the host CPU,
# both with activations and KV cache in f32 and matmuls at HIGHEST
# precision. What remains is summation order and the chip's exp/rsqrt
# approximations, ~1e-6 per op and ~1e-5 after 24 layers; 1e-3 leaves two
# orders of magnitude and still fails on one flipped top-8 expert choice or
# a wrong cache write (>= 1e-2). The bf16 serving path is not compared: a
# bf16 step differs by a few bf16 steps per layer between the backends,
# enough to flip expert choices near a tie, and then logits legitimately
# differ by several percent (6.1e-2 with 81% argmax agreement on the chip).
SERVE_LOGIT_RTOL = 1e-3
# flash_attention in bf16: the output is rounded to bf16 and p is cast to
# bf16 before p @ v in kernel and oracle alike; same bound as the
# interpret-mode tests.
FLASH_TOL = 3e-2
# wkv6: f32 throughout, matmuls at HIGHEST precision on both sides; only
# summation order and exp/log rounding differ (same bound as the tests).
WKV6_RTOL = 1e-4
# Grad sync, hierarchical plain vs flat, one step from the same
# parameters: the same f32 sum in another order, ~1e-7 relative per
# element. The update (SGD, lr 1, clipped to norm 1) is ~1.6e-4 per element
# on parameters of ~2e-2, whose f32 rounding leaves ~1e-5 of it; 1e-4
# bounds both.
SYNC_PLAIN_RTOL = 1e-4
# int8 pod hop: each shard is rounded to 255 levels of its largest entry
# (scale s = amax / 127), so each element is off by at most s over the two
# pods, ~s / sqrt(6) rms. Relative to the shard's rms that is
# crest / (127 * sqrt(6)) ~ crest * 3.2e-3, where crest = amax / rms;
# 0.2 holds gradient shards with a crest factor up to ~60 (the first
# four-chip run implied ~0.06, a crest near 19).
SYNC_INT8_RTOL = 2e-1


def device_tag(dev) -> str:
    return f"{dev.platform}:{dev.device_kind}"


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check(name: str, value: float, bound: float) -> None:
    if not value <= bound:  # also catches NaN
        raise AssertionError(f"{name}: {value!r} exceeds {bound!r}")


# ---------------------------------------------------------------- serving

def serve_phase(cfg, *, batch: int, prompt_len: int, gen: int,
                check_steps: int, seed: int) -> None:
    """Serve one batch through ``serve_batch`` on the default device, then
    serve its f32 twin and check the twin's first ``check_steps`` logits
    against the same jitted step on the host CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import make_decode_fn, serve_batch
    from repro.models import lm

    dev = jax.devices()[0]
    tag = device_tag(dev)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lm.init, static_argnums=0)(cfg, jax.random.key(seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[serve] {cfg.arch_id}: {cfg.n_layers} layers d_model "
          f"{cfg.d_model} vocab {cfg.vocab_size} experts "
          f"{cfg.moe.n_experts if cfg.moe else 0}, {n_params} params "
          f"initialised in {time.perf_counter() - t0:.2f}s on {tag}")

    out = serve_batch(cfg, batch=batch, prompt_len=prompt_len, gen=gen,
                      seed=seed, params=params, keep_logits=1, quiet=True)
    stats = dev.memory_stats() or {}
    print(f"[serve] {out['tokens'].size} tokens generated (batch {batch}, "
          f"prompt {prompt_len}, gen {gen}, {cfg.compute_dtype}) on {tag}")
    print(f"[serve] compile_s={out['compile_s']:.3f} prefill_s="
          f"{out['prefill_s']:.4f} decode_s={out['decode_s']:.4f} "
          f"decode_tok_per_s={out['decode_tok_per_s']:.1f} on {tag} "
          f"(single smoke run, not a benchmark)")
    print(f"[serve] peak_bytes_in_use={stats.get('peak_bytes_in_use')} on "
          f"{tag}")
    if out["tokens"].shape != (batch, gen):
        raise AssertionError(f"token shape {out['tokens'].shape}")
    if not np.isfinite(np.asarray(out["logits"][0])).all():
        raise AssertionError("non-finite logits from the served batch")

    twin = dataclasses.replace(cfg, compute_dtype="float32",
                               kv_cache_dtype="float32")
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        out = serve_batch(twin, batch=batch, prompt_len=prompt_len,
                          gen=check_steps, seed=seed, params=params,
                          keep_logits=check_steps, quiet=True)
        chip_logits = np.stack([np.asarray(x) for x in out["logits"]])
        # the same jitted step, teacher-forced with the chip's tokens
        t0 = time.perf_counter()
        host_params = jax.device_put(params, cpu)
        state = jax.device_put(
            lm.decode_state_init(twin, batch, prompt_len + check_steps), cpu)
        step = make_decode_fn(twin)
        feed = np.concatenate([out["prompts"],
                               out["tokens"][:, :check_steps - 1]], axis=1)
        ref = []
        for i in range(feed.shape[1]):
            logits, state = step(
                host_params, state, jax.device_put(feed[:, i:i + 1], cpu),
                jax.device_put(jnp.full((batch,), i, jnp.int32), cpu))
            if i >= prompt_len - 1:
                ref.append(np.asarray(logits))
    ref = np.stack(ref)
    err = rel_err(chip_logits, ref)
    agree = float((chip_logits.argmax(-1) == ref.argmax(-1)).mean())
    print(f"[serve] f32 twin logits on {tag} vs {device_tag(cpu)} over "
          f"{check_steps} steps: max|diff|/max|ref|={err:.3e} (bound "
          f"{SERVE_LOGIT_RTOL}), argmax agreement {agree:.3f}, reference "
          f"took {time.perf_counter() - t0:.1f}s")
    if not np.isfinite(chip_logits).all():
        raise AssertionError("non-finite logits from the f32 twin")
    check("serve logits vs cpu", err, SERVE_LOGIT_RTOL)


# ---------------------------------------------------------------- kernels

def run_kernel(name: str, fn, args, ref, compare) -> None:
    """Lower ``fn`` once, require a Mosaic kernel in it where the backend
    is a TPU, run it and compare with ``ref`` computed on the same
    device."""
    import jax
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    compiled_kernel = "tpu_custom_call" in lowered.as_text()
    if dev.platform == "tpu" and not compiled_kernel:
        raise AssertionError(f"{name} did not lower to a Mosaic kernel")
    exe = lowered.compile()
    t_compile = time.perf_counter() - t0
    jax.block_until_ready(exe(*args))
    t0 = time.perf_counter()
    got = jax.block_until_ready(exe(*args))
    t_run = time.perf_counter() - t0
    want = jax.block_until_ready(ref(*args))
    detail = compare(got, want)
    mode = "compiled" if compiled_kernel else "interpreted"
    print(f"[kernel] {name}: {mode} on {device_tag(dev)}, compile_s="
          f"{t_compile:.3f} run_s={t_run:.6f} (single smoke run), "
          f"matches ref.py: {detail}")


def cellcopy_phase(*, n_cells: int, cell_bytes: int, block_cells: int,
                   seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.cellcopy.kernel import cellcopy
    from repro.kernels.cellcopy.ops import verify
    from repro.kernels.cellcopy.ref import cellcopy_ref

    src = jax.random.bits(jax.random.key(seed), (n_cells, cell_bytes // 4),
                          jnp.uint32).view(jnp.int32)

    def compare(got, want):
        (dst, sums), (rdst, rsums) = got, want
        if not (bool(jnp.array_equal(dst, rdst))
                and bool(jnp.array_equal(sums, rsums))
                and bool(verify(dst, sums))):
            raise AssertionError("cellcopy differs from cellcopy_ref")
        return (f"{n_cells} cells of {cell_bytes} B, block_cells "
                f"{block_cells}, copy and checksums bit-exact")

    run_kernel("cellcopy",
               lambda x: cellcopy(x, block_cells=block_cells), (src,),
               cellcopy_ref, compare)


def flash_phase(*, b: int, h: int, kv: int, s: int, d: int,
                seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, kv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, kv, s, d), jnp.bfloat16)

    def compare(got, want):
        diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        bound = FLASH_TOL * (1 + jnp.abs(want.astype(jnp.float32)))
        worst = float(jnp.max(diff - bound))
        if not worst <= 0:
            raise AssertionError(f"flash_attention off by {worst} beyond "
                                 f"rtol=atol={FLASH_TOL}")
        return (f"B={b} H={h} KV={kv} S={s} D={d} bf16 causal, max|diff|="
                f"{float(diff.max()):.3e} within rtol=atol={FLASH_TOL}")

    run_kernel("flash_attention", flash_attention, (q, k, v),
               attention_ref, compare)


def wkv6_phase(*, b: int, h: int, s: int, n: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.rwkv6.kernel import wkv6
    from repro.kernels.rwkv6.ref import wkv6_ref

    ks = jax.random.split(jax.random.key(seed), 5)
    r, k, v = (jax.random.normal(ks[i], (b, h, s, n)) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, h, s, n)) * 0.5
                         - 2.0))
    u = jax.random.normal(ks[4], (h, n)) * 0.5

    def ref(*args):
        with jax.default_matmul_precision("highest"):
            return wkv6_ref(*args)

    def compare(got, want):
        err = rel_err(got, want)
        check("wkv6 vs wkv6_ref", err, WKV6_RTOL)
        return (f"B={b} H={h} S={s} n={n}, max|diff|/max|ref|={err:.3e} "
                f"(bound {WKV6_RTOL})")

    run_kernel("wkv6", wkv6, (r, k, v, w, u), ref, compare)


# ------------------------------------------------------ four-chip grad sync

def grad_sync_phase(cfg, *, seq_len: int, global_batch: int, steps: int,
                    seed: int) -> None:
    """Train ``cfg`` for ``steps`` steps with make_cmpi_train_step on a
    flat (4,) ("data",) mesh, and take each step also on a (2, 2) ("pod",
    "data") mesh with sync_grads plain and int8, from the flat run's
    parameters, so that each comparison sees one synchronisation and no
    compounding (at lr 1 a 1e-7 difference grows to 1e-3 in three steps).

    The optimizer is SGD at lr 1 with the default clip to norm 1, so the
    parameter update is the synchronised (clipped) gradient itself; Adam
    would turn every element into about +-lr and hide a wrong sum."""
    import jax
    import numpy as np
    from repro.configs import InputShape
    from repro.distributed.schedules import make_cmpi_train_step
    from repro.launch.mesh import make_test_mesh
    from repro.models import lm
    from repro.train import data as D
    from repro.train import optimizer as opt

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(devs)}")
    tag = f"{device_tag(devs[0])} x4"
    shape = InputShape("smoke", "train", seq_len, global_batch)
    oc = opt.OptConfig(name="sgd", lr=1.0, warmup_steps=0)
    ds = D.SyntheticLM(D.for_model(cfg, shape, seed))
    batches = [ds.batch(i) for i in range(steps)]
    params = jax.device_get(jax.jit(lm.init, static_argnums=0)(
        cfg, jax.random.key(seed)))
    ostate = opt.init(oc, params)
    print(f"[sync] {cfg.arch_id}: {cfg.n_layers} layers d_model "
          f"{cfg.d_model} vocab {cfg.vocab_size}, batch {global_batch} x "
          f"{seq_len} tokens, {steps} SGD steps on {tag}")

    variants = {
        "flat": (make_test_mesh((4,), ("data",)), "none"),
        "hier-plain": (make_test_mesh((2, 2), ("pod", "data")), "none"),
        "hier-int8": (make_test_mesh((2, 2), ("pod", "data")), "int8"),
    }

    def build(mesh, comp):
        fn, in_sh, out_sh = make_cmpi_train_step(cfg, shape, mesh, oc=oc,
                                                 compression=comp)
        abstract = [jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh), tree, shs)
            for tree, shs in zip((params, ostate, batches[0]), in_sh)]
        t0 = time.perf_counter()
        exe = jax.jit(fn, in_shardings=in_sh,
                      out_shardings=out_sh).lower(*abstract).compile()
        return exe, in_sh, time.perf_counter() - t0

    # XLA compiles outside the interpreter lock: the three programs
    # compile side by side on the host's cores
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(variants)) as ex:
        futures = {name: ex.submit(build, *v) for name, v in variants.items()}
        built = {name: f.result() for name, f in futures.items()}
    print(f"[sync] compiled {len(built)} train steps in "
          f"{time.perf_counter() - t0:.1f}s (each "
          f"{[round(b[2], 1) for b in built.values()]}s) for {tag}")

    def dist(a, b):
        return np.sqrt(sum(float(np.sum(np.square(
            x.astype(np.float64) - y, dtype=np.float64)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))

    bounds = {"hier-plain": SYNC_PLAIN_RTOL, "hier-int8": SYNC_INT8_RTOL}
    for i, hb in enumerate(batches):
        out = {}
        for name, (exe, in_sh, _) in built.items():
            p, o = jax.device_put((params, ostate), in_sh[:2])
            t0 = time.perf_counter()
            p, o, m = exe(p, o, jax.device_put(hb, in_sh[2]))
            loss, gn = float(m["loss"]), float(m["grad_norm"])
            t_step = time.perf_counter() - t0
            mesh = variants[name][0]
            in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in mesh.devices.flat]
            print(f"[sync] step {i} {name}: mesh {dict(mesh.shape)} loss "
                  f"{loss:.6f} grad_norm {gn:.6f} step_s={t_step:.4f} "
                  f"bytes_in_use per device {in_use} on {tag} (single "
                  f"smoke run)")
            if devs[0].platform == "tpu" and not all(in_use):
                raise AssertionError(f"{name}: a device holds no memory")
            if not np.isfinite([loss, gn]).all():
                raise AssertionError(f"{name}: non-finite loss or grad norm")
            out[name] = (jax.device_get(p), jax.device_get(o), loss, gn)
            del p, o
        p_f, o_f, loss_f, gn_f = out["flat"]
        update = dist(p_f, params)
        for name, bound in bounds.items():
            p, _, loss, gn = out[name]
            errs = {"update": dist(p, p_f) / update,
                    "loss": abs(loss - loss_f) / abs(loss_f),
                    "grad_norm": abs(gn - gn_f) / gn_f}
            print(f"[sync] step {i} {name} vs flat: " + " ".join(
                f"{k} {v:.3e}" for k, v in errs.items())
                + f" (bound {bound}; |update|={update:.4f})")
            for what, e in errs.items():
                check(f"step {i} {name} {what} vs flat", e, bound)
        params, ostate = p_f, o_f


# ------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip gradient-sync comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # The serving check runs its reference on the host CPU backend, so keep
    # that backend available where JAX_PLATFORMS names only the chip.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax.devices()[0] is "
              f"platform {dev.platform!r} ({dev.device_kind}); nothing "
              f"was run", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    n_dev = len(jax.devices())
    print(f"[device] {dev.platform} {dev.device_kind} x{n_dev}, jax "
          f"{jax.__version__}, compile cache {cache_dir}")

    t0 = time.perf_counter()
    if args.four_chips:
        grad_sync_phase(get_config("smollm-135m"), seq_len=512,
                        global_batch=8, steps=3, seed=args.seed)
    else:
        serve_phase(get_config("granite-moe-1b-a400m"), batch=4,
                    prompt_len=32, gen=32, check_steps=4, seed=args.seed)
        cellcopy_phase(n_cells=256, cell_bytes=64 * 1024, block_cells=8,
                       seed=args.seed)
        glm = get_config("glm4-9b")
        flash_phase(b=1, h=glm.n_heads, kv=glm.n_kv_heads, s=4096,
                    d=glm.d_head, seed=args.seed)
        rwkv = get_config("rwkv6-3b")
        wkv6_phase(b=1, h=rwkv.d_model // rwkv.rwkv.head_size, s=4096,
                   n=rwkv.rwkv.head_size, seed=args.seed)
    print(f"[cache] {cache_dir}: {cache['hits']} hits, {cache['misses']} "
          f"misses; all phases {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
