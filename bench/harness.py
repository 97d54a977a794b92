"""Runs one cell of the benchmark once and prints its result line.

Everything that belongs to one cell is found by name:

- ``bench/workloads/<cell>.json``: configuration, traffic, chips, why;
- ``bench/traffic/<traffic>.json``: the driver and its traffic parameters;
- ``bench/configs/<config>.json``: the configuration as it is run, with the
  registry architecture it overrides and its plain reference;
- ``bench/drivers/<driver>.py``: ``run(run) -> Outcome`` drives one program
  path through set-up, the measured window and the correctness check;
- ``bench/references/<reference>.py``: the plain reference and the
  weights it makes;
- ``bench/metrics/<metric>.py``: ``read(ctx) -> float | None`` and
  ``UNIT`` (and optionally ``note(ctx) -> str``, printed beside it), one
  per-layer metric, read from the reduced trace.

Adding a cell, a configuration, a driver or a metric adds files; it edits
none.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class Config(dict):
    """A configuration file's contents, hashable so that jitted functions
    can take it as a static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def percentile(values, q: float) -> float:
    """The q-th percentile of all values (linear interpolation)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


# ------------------------------------------------------------ compiles

class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) and persistent-cache misses, through ``jax.monitoring``."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.built = 0
        self.misses = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **kw):
            if event == self._event:
                self.built += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.built, self.misses


# ------------------------------------------------------------ the run

@dataclass
class Check:
    """One number compared with its limit: correct when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


@dataclass
class Outcome:
    attempted: int
    failed: int
    metrics: dict                      # end-to-end: name -> (value, unit)
    checks: list
    facts: dict = field(default_factory=dict)   # for per-layer readers


@dataclass
class Run:
    """What a driver gets: the specs, the devices, the clocks, the trace."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    started: float                     # perf_counter at process start
    require_tpu: bool = True
    spec_dir: Path = BENCH

    def __post_init__(self):
        self.cell = self._json("workloads", self.workload)
        self.traffic = self._json("traffic", self.cell["traffic"])
        self.config = Config(self._json("configs", self.cell["config"]))
        self.chips = int(self.cell["chips"])
        self.setup_s = None
        self.trace_dir = None
        self._reference = None
        self._phases = []
        self._last_mark = self.started

    def _json(self, kind, name):
        return json.loads((self.spec_dir / kind / f"{name}.json").read_text())

    # devices ------------------------------------------------------
    def claim_devices(self):
        import jax
        self.mark("imports")
        devs = jax.devices()
        if self.require_tpu and devs[0].platform != "tpu":
            raise NoDevice(f"JAX found no TPU (platform "
                           f"{devs[0].platform!r}); this benchmark measures "
                           f"the chip only")
        if len(devs) < self.chips:
            raise NoDevice(f"cell {self.workload} needs {self.chips} chips, "
                           f"JAX found {len(devs)}")
        self.devices = devs[: self.chips]
        d = self.devices[0]
        print(f"[bench] device platform={d.platform} kind={d.device_kind!r} "
              f"count={len(self.devices)} (of {len(devs)})", flush=True)
        self.compiles = CompileCounter()
        self.mark("devices")
        return self.devices

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))

    # program configuration -----------------------------------------
    def program_config(self):
        """The registry configuration with the file's overrides, checked
        against every size the file states."""
        from repro.configs import get_config
        c = self.config
        cfg = get_config(c["arch"])
        for key, val in c.get("overrides", {}).items():
            if "." in key:
                outer, inner = key.split(".")
                val = dataclasses.replace(getattr(cfg, outer), **{inner: val})
                key = outer
            cfg = dataclasses.replace(cfg, **{key: val})
        stated = {
            "d_model": c["hidden_size"], "n_layers": c["num_hidden_layers"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "d_head": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"],
            "vocab_pad_multiple": c["vocab_pad_multiple"],
            "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"],
            "param_dtype": c["param_dtype"],
            "compute_dtype": c["compute_dtype"],
            "kv_cache_dtype": c["kv_cache_dtype"],
        }
        if c.get("num_local_experts"):
            stated["moe.n_experts"] = c["num_local_experts"]
            stated["moe.top_k"] = c["num_experts_per_tok"]
        for key, want in stated.items():
            got = cfg
            for part in key.split("."):
                got = getattr(got, part)
            if got != want:
                raise ValueError(f"{c['name']}: the program runs {key}="
                                 f"{got!r}, the configuration file states "
                                 f"{want!r}")
        return cfg

    def reference(self):
        if self._reference is None:
            self._reference = load_module("references",
                                          self.config["reference"])
        return self._reference

    def make_params(self, cfg, out_shardings=None):
        """The reference's weights for this seed, made on the device in
        one jitted call, after checking that they have the layout and
        dtypes of the program's own parameters."""
        import jax
        from repro.models import lm
        ref = self.reference()
        key = ref.seed_key(self.seed)
        want = jax.eval_shape(lambda: lm.init(cfg, key))
        made = jax.eval_shape(lambda: ref.make_params(self.config, key))

        def layout(tree):
            return (jax.tree.structure(tree),
                    [(x.shape, x.dtype) for x in jax.tree.leaves(tree)])

        if layout(want) != layout(made):
            raise ValueError("the reference's weights do not have the "
                             "layout of the program's parameters")
        gen = jax.jit(ref.make_params, static_argnums=0,
                      out_shardings=out_shardings)
        return gen(self.config, key)

    # clocks --------------------------------------------------------
    def mark(self, phase: str):
        """Ends a phase of set-up; ``setup_done`` prints each one's time."""
        now = time.perf_counter()
        self._phases.append((phase, now - self._last_mark))
        self._last_mark = now

    def setup_done(self):
        self.setup_s = time.perf_counter() - self.started
        self._compiles_at_window = self.compiles.snapshot()
        built, misses = self._compiles_at_window
        phases = ", ".join(f"{k} {s:.3f} s" for k, s in self._phases)
        print(f"[setup] {self.setup_s:.3f} s: {phases}; {built} executables "
              f"built, {misses} compile-cache misses", flush=True)
        # what set-up made lives for the whole run: keep the collector
        # from scanning it again in the window, where a full collection of
        # a JAX process's objects stalls the client for tens of ms or more
        gc.collect()
        gc.freeze()

    def window_done(self):
        b0, m0 = self._compiles_at_window
        b1, m1 = self.compiles.snapshot()
        print(f"[bench] in the window: {b1 - b0} executables built, "
              f"{m1 - m0} compile-cache misses", flush=True)

    # trace ---------------------------------------------------------
    def trace_start(self):
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def trace_stop(self):
        import jax
        jax.profiler.stop_trace()


# ------------------------------------------------------------ one cell

def execute(run: Run) -> dict:
    """Set up, measure, check; returns the result line's object."""
    run.claim_devices()
    driver = load_module("drivers", run.traffic["driver"])
    out: Outcome = driver.run(run)
    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": out.facts.get("memory_peak_bytes", 0)}
    result = {"correct": all(c.ok for c in out.checks) and bool(out.checks),
              "attempted": out.attempted, "failed": out.failed}
    if run.trace:
        result["metrics"], breakdown = read_per_layer(run, out, device)
    else:
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in out.metrics.items()}
        result["metrics"]["setup_s"] = {"value": run.setup_s, "unit": "s"}
    result["device"] = device
    if run.trace and breakdown:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result


def read_per_layer(run: Run, out: Outcome, device: dict):
    from bench import xplane
    peaks = device_peaks(device["kind"])
    summary = None
    if run.trace_dir is not None:      # None: the window ended before it
        try:
            summary = xplane.summarize(xplane.find(run.trace_dir),
                                       out.facts["spans"])
        finally:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
    metrics = {}
    ctx = xplane.Reading(summary=summary, facts=out.facts, peaks=peaks,
                         config=run.config, traffic=run.traffic)
    for path in sorted((BENCH / "metrics").glob("*.py")):
        mod = load_module("metrics", path.stem)
        value = mod.read(ctx)
        if value is not None:
            metrics[path.stem] = {"value": value, "unit": mod.UNIT}
            if hasattr(mod, "note"):
                print(f"[metric] {path.stem}: {mod.note(ctx)}", flush=True)
    breakdown = None
    if summary is not None and summary.window_s > 0:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops[:10],
                     "idle_gaps": summary.idle_by_span[:10]}
    return metrics, breakdown


def device_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][kind]


def report(result: dict) -> None:
    """Print the compared numbers last on standard error, and the result
    as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
