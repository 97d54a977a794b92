"""Data-parallel training through the program's explicit-sync train step.

``repro.distributed.schedules.make_cmpi_train_step`` on a mesh of the
traffic file (("pod", "data") = (2, 2) on four chips): each chip computes
the gradient of its rows, ``sync_grads`` reduce-scatters it in the pod,
all-reduces the shard across pods and all-gathers it in the pod, and every
chip applies the same AdamW update. Batches come from the program's
``train.data.SyntheticLM`` for the seed, through its background
``Prefetcher``, inside the window: the input pipeline is part of what a
step costs.

Set-up builds the step once (jitted, parameters and optimizer state
donated), makes the weights from the seed on the device, and drives that
same step through its first three steps, on the window's own feed; the
window then continues from the fourth. Those three steps are what the
check compares with the plain reference, once the window has closed and
the program's state is freed: each step's loss, the first gradient as
the optimizer got it (from its first moment after one step, unclipped
by the gradient norm the step reported), and each leaf's change over the
three steps, each leaf by the gap of its norm.
"""
from __future__ import annotations

import math
import time
from functools import partial

import numpy as np

from bench import work
from bench.harness import Check, Outcome

CHECK_STEPS = 3


def leaf_norms(tree) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree.leaves(t)]))(tree), np.float64)


def diff_norms(a, b) -> np.ndarray:
    import jax
    return leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   counted: np.ndarray) -> float:
    """max over counted leaves of | |prog| - |ref| | / max(|ref|, median
    |ref|)."""
    base = np.maximum(ref, np.median(ref[counted]))
    return float(np.max(np.abs(prog - ref)[counted] / base[counted]))


class Trainer:
    """The program's train step on the traffic's mesh, jitted once with
    parameters and optimizer state donated, and its feed."""

    def __init__(self, run, cfg):
        import jax
        from jax.sharding import AxisType
        from repro.configs import InputShape
        from repro.distributed.schedules import make_cmpi_train_step
        from repro.train import optimizer as opt

        tr = run.traffic
        self.run, self.cfg, self.opt = run, cfg, opt
        self.oc = opt.for_model(cfg)
        for key, want in run.config["optimizer"].items():
            if getattr(self.oc, key) != want:
                raise ValueError(f"the program's optimizer has {key}="
                                 f"{getattr(self.oc, key)!r}, the "
                                 f"configuration states {want!r}")
        axes = tuple(tr["mesh"]["axes"])
        mesh = jax.make_mesh(tuple(tr["mesh"]["shape"]), axes,
                             devices=run.devices,
                             axis_types=(AxisType.Auto,) * len(axes))
        self.S, self.GB = tr["seq_len"], tr["global_batch"]
        shape = InputShape("bench", "train", self.S, self.GB)
        fn, self.in_sh, out_sh = make_cmpi_train_step(
            cfg, shape, mesh, compression=tr["compression"])
        self.step = jax.jit(fn, in_shardings=self.in_sh,
                            out_shardings=out_sh, donate_argnums=(0, 1))

    def start(self):
        """Weights from the seed, a fresh optimizer state and the feed."""
        import jax
        from repro.train import data as D
        params = self.run.make_params(self.cfg, out_shardings=self.in_sh[0])
        opt_state = jax.jit(partial(self.opt.init, self.oc),
                            out_shardings=self.in_sh[1])(params)
        ds = D.SyntheticLM(D.DataConfig(
            vocab_size=self.cfg.vocab_size, seq_len=self.S,
            global_batch=self.GB, seed=self.run.seed))
        return params, opt_state, D.Prefetcher(ds, 0, depth=2)

    def feed(self, feeder):
        import jax
        with jax.profiler.TraceAnnotation("data"):
            _, host = feeder.next()
            return host, jax.device_put(host, self.in_sh[2])

    def first_steps(self, params, opt_state, feeder):
        """The first steps through the step and feed the window uses, and
        what the check compares: their losses, the first gradient's norm
        by leaf as the optimizer got it, each leaf's change."""
        seen = {"host_batches": [], "losses": []}
        for k in range(CHECK_STEPS):
            host, batch = self.feed(feeder)
            seen["host_batches"].append(host)
            params, opt_state, m = self.step(params, opt_state, batch)
            seen["losses"].append(float(m["loss"]))
            if k == 0:
                unclip = max(1.0, float(m["grad_norm"]) / self.oc.grad_clip)
                seen["first_grad"] = leaf_norms(opt_state["mu"]) \
                    / (1 - self.oc.b1) * unclip
        start = self.run.make_params(self.cfg, out_shardings=self.in_sh[0])
        seen["change"] = diff_norms(params, start)
        return params, opt_state, seen


def stop(feeder):
    feeder.stop()
    feeder.thread.join(timeout=60)


def run(run) -> Outcome:
    import jax
    tr = run.traffic
    cfg = run.program_config()
    trainer = Trainer(run, cfg)
    params, opt_state, feeder = trainer.start()
    try:
        params, opt_state, seen = trainer.first_steps(params, opt_state,
                                                      feeder)
        run.setup_done()

        completed = attempted = failed = 0
        t_end = time.perf_counter() + run.seconds
        # traced: steps after the feed's queue has drained, as most of
        # the window runs
        trace_first = tr["trace_first_step"]
        trace_last = trace_first + tr["trace_steps"] - 1
        while time.perf_counter() < t_end:
            if run.trace and attempted == trace_first:
                run.trace_start()
            host, batch = trainer.feed(feeder)
            with jax.profiler.TraceAnnotation("train_step"):
                params, opt_state, m = trainer.step(params, opt_state, batch)
            with jax.profiler.TraceAnnotation("host_read"):
                loss = float(m["loss"])
            if run.trace and attempted == trace_last:
                run.trace_stop()
            attempted += 1
            failed += not math.isfinite(loss)
            completed += time.perf_counter() <= t_end
        if run.trace and trace_first < attempted <= trace_last:
            run.trace_stop()
        run.window_done()
        peak = run.memory_peak()
    finally:
        stop(feeder)
    del params, opt_state

    S, GB = trainer.S, trainer.GB
    metrics = {"train_tok_s": (completed * GB * S / run.seconds, "tokens/s")}
    checks = reference_checks(run, cfg, seen)
    facts = {
        "memory_peak_bytes": peak,
        "kind": "train",
        "spans": ("data", "train_step", "host_read"),
        "step_program": "local_step",
        "chips": len(run.devices),
        "steps_traced": trace_last - trace_first + 1,
        "flops_per_step": work.train_step_flops(run.config, GB, S),
        "tokens_per_step": GB * S,
    }
    return Outcome(attempted, failed, metrics, checks, facts)


def reference_checks(run, cfg, seen) -> list:
    """The plain reference's first steps from the same weights and rows,
    compared with what the program's step gave (``seen``)."""
    return compare(seen, reference_run(run, cfg, seen["host_batches"]),
                   run.traffic["limits"])


def reference_run(run, cfg, host_batches, **variant) -> dict:
    """The reference's losses, first gradient (before clipping) by leaf and
    change by leaf over ``host_batches``, from the seed's weights, rows
    spread over the chips. ``variant`` goes to the reference's
    ``train_steps``: the control and the planted faults."""
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    ref = run.reference()
    rows = jax.make_mesh((len(run.devices),), ("rows",), devices=run.devices,
                         axis_types=(AxisType.Auto,))
    start = run.make_params(cfg, out_shardings=NamedSharding(rows, P()))
    losses, grad, end = ref.train_steps(
        run.config, run.config["optimizer"], start,
        [(b["tokens"], b["labels"]) for b in host_batches],
        rows_per_call=run.traffic["reference_rows_per_call"],
        shard=NamedSharding(rows, P("rows")), **variant)
    return {"losses": losses, "first_grad": leaf_norms(grad),
            "change": diff_norms(end, start)}


def compare(seen: dict, ref: dict, limits: dict) -> list:
    """The three numbers of the check. Leaves whose reference gradient is
    under a thousandth of the median leaf's move under Adam by round-off
    alone and are not compared."""
    counted = ref["first_grad"] >= 1e-3 * np.median(ref["first_grad"])
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(seen["losses"], ref["losses"]))
    return [
        Check("loss_gap", float(loss_gap), limits["loss_gap"]),
        Check("first_grad_gap", worst_leaf_gap(
            seen["first_grad"], ref["first_grad"], counted),
            limits["first_grad_gap"]),
        Check("update_gap", worst_leaf_gap(
            seen["change"], ref["change"], counted), limits["update_gap"]),
    ]
