"""Static-batch serving through the program's one-token step.

The client and batcher follow ``repro.launch.serve.serve_batch``: a batch
of ``slots`` requests is admitted into a fresh decode state
(``lm.decode_state_init``); each prompt is prefilled as teacher-forced
steps of the program's serving step (``make_decode_fn`` ->
``lm.decode_step``); then each step's logits are picked greedily on the
device, the token is read to the host as a streaming client must, and fed
to the next step. When every request of the batch has its tokens, the
next batch is admitted: a closed loop with a full queue. Unlike
``serve_batch``, the client keeps at most ``PREFILL_AHEAD`` prefill steps
queued ahead of the device, as a server bounds its queue: the device
stays as busy, and the host does not queue 128 steps that each make a new
decode state (the step does not donate it).

The step and the pick are built once, in set-up, and warmed on the
cell's only shapes; nothing compiles in the window. The window runs for
``--seconds``; the batch in flight at its close is served to its end, so
that every request admitted in the window has its first token and all its
gaps, but only tokens on the host before the close count for the rate.

Correctness: once the window has closed and the program's state is freed,
a sample of the finished requests (drawn from the seed) is run through
the plain reference in float32 over each prompt and its served tokens.
At each served token, the gap by which the reference's logit for it lies
below the reference's best there; their mean over the sample is compared
with the limit of the traffic file.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import loadgen
from bench.harness import Check, Outcome, percentile

PREFILL_AHEAD = 2


class Server:
    """The program's serving step and the client's greedy pick, built once
    and warmed on the traffic's only shapes."""

    def __init__(self, run, cfg):
        import jax
        import jax.numpy as jnp
        from repro.launch.serve import make_decode_fn
        from repro.models import lm

        tr = run.traffic
        self.plen, self.olen = loadgen.lengths(tr)
        self.slots, self.slot_ctx = tr["slots"], tr["slot_ctx"]
        if self.plen + self.olen - 1 > self.slot_ctx:
            raise ValueError(f"{self.plen} + {self.olen} - 1 positions "
                             f"overflow a slot of {self.slot_ctx}")
        self.cfg, self.lm = cfg, lm
        self.ahead = PREFILL_AHEAD
        self.decode_fn = make_decode_fn(cfg)
        self.pick = jax.jit(
            lambda logits: jnp.argmax(logits, -1).astype(jnp.int32))

    def pos_at(self, i):
        import jax.numpy as jnp
        return jnp.asarray(np.full((self.slots,), i, np.int32))

    def warm(self, params):
        """A fresh state, a prefill step, a pick read to the host, a decode
        step fed with the pick: every call the window makes."""
        import jax.numpy as jnp
        state = self.lm.decode_state_init(self.cfg, self.slots, self.slot_ctx)
        logits, state = self.decode_fn(
            params, state, jnp.asarray(np.zeros((self.slots, 1), np.int32)),
            self.pos_at(0))
        nxt = self.pick(logits)
        np.asarray(nxt)
        logits, state = self.decode_fn(params, state, nxt[:, None],
                                       self.pos_at(1))
        np.asarray(self.pick(logits))

    def serve(self, params, reqs):
        """One static batch to its end: (admission time, host time of each
        output token (olen,), tokens (slots, olen), the host's longest
        waits (``HostWaits``))."""
        import jax
        import jax.numpy as jnp
        ann = jax.profiler.TraceAnnotation
        waits = HostWaits()
        with ann("batch_admit"):
            t_admit = time.perf_counter()
            prompts = np.stack([r.prompt for r in reqs])
            state = self.lm.decode_state_init(self.cfg, self.slots,
                                              self.slot_ctx)
        waits.last = t_admit          # admission counts as "between" at 0
        times = np.empty(self.olen)
        out = np.empty((self.slots, self.olen), np.int32)
        with ann("prefill"):
            queued = []
            for i in range(self.plen):
                t0 = time.perf_counter()
                logits, state = self.decode_fn(
                    params, state, jnp.asarray(prompts[:, i:i + 1]),
                    self.pos_at(i))
                waits.add("step_call", i, t0)
                queued.append(logits)
                if len(queued) > self.ahead:
                    t0 = time.perf_counter()
                    queued.pop(0).block_until_ready()
                    waits.add("prefill_wait", i, t0)
        for j in range(self.olen):
            nxt = self.pick(logits)
            with ann("host_read"):
                t0 = time.perf_counter()
                out[:, j] = np.asarray(nxt)
                times[j] = waits.add("token_read", self.plen + j, t0)
            if j + 1 < self.olen:
                with ann("decode"):
                    t0 = time.perf_counter()
                    logits, state = self.decode_fn(
                        params, state, nxt[:, None],
                        self.pos_at(self.plen + j))
                    waits.add("step_call", self.plen + j, t0)
        return t_admit, times, out, waits


class HostWaits:
    """How late the host runs: the longest host time inside a call of the
    step (its dispatch), inside a token read (the step's device time and
    the copy), and between the two (the client's own Python), each with
    the position at which it fell."""

    def __init__(self):
        self.longest = {}
        self.last = time.perf_counter()

    def add(self, kind: str, pos: int, t0: float) -> float:
        t1 = time.perf_counter()
        for k, s in ((kind, t1 - t0), ("between", t0 - self.last)):
            if s > self.longest.get(k, (0.0, 0))[0]:
                self.longest[k] = (s, pos)
        self.last = t1
        return t1

    def line(self) -> str:
        return ", ".join(f"{k} {1e3 * s:.1f} ms at {p}"
                         for k, (s, p) in sorted(self.longest.items()))


def run(run) -> Outcome:
    import jax
    tr = run.traffic
    cfg = run.program_config()
    server = Server(run, cfg)
    slots, plen, olen = server.slots, server.plen, server.olen
    run.mark("step")
    params = jax.block_until_ready(run.make_params(cfg))
    run.mark("weights")
    server.warm(params)
    run.mark("warm")
    run.setup_done()

    batches = []          # (t_admit, token times (olen,), requests, tokens)
    t_end = time.perf_counter() + run.seconds
    while time.perf_counter() < t_end:
        traced = run.trace and not batches
        if traced:
            run.trace_start()
        with jax.profiler.TraceAnnotation("batch_admit"):
            reqs = batch_requests(run, len(batches), slots, cfg.vocab_size)
        t_admit, times, out, waits = server.serve(params, reqs)
        if traced:
            run.trace_stop()
        batches.append((t_admit, times, reqs, out))
        print(f"[serve] batch {len(batches) - 1}: ttft "
              f"{times[0] - t_admit:.4f} s; longest host waits: "
              f"{waits.line()}", file=sys.stderr, flush=True)
    run.window_done()
    peak = run.memory_peak()

    # end-to-end metrics over every request admitted in the window
    ttft = np.concatenate([np.full(slots, t[0] - a) for a, t, _, _ in batches])
    gaps = np.concatenate([np.tile(np.diff(t), slots)
                           for _, t, _, _ in batches])
    delivered = sum(slots * int(np.sum(t < t_end)) for _, t, _, _ in batches)
    metrics = {
        "output_tok_s": (delivered / run.seconds, "tokens/s"),
        "ttft_p95_ms": (1e3 * percentile(ttft, 95), "ms"),
        "itl_p95_ms": (1e3 * percentile(gaps, 95), "ms"),
    }

    steps_per_batch = plen + olen - 1
    # the check, once the program's decode state is gone
    pool = [(r, toks) for _, _, reqs, out in batches
            for r, toks in zip(reqs, out)]
    sample = check_sample(run, pool)
    gaps = token_gaps(reference_logits(run, params, sample, plen, olen),
                      [toks for _, toks in sample])
    # the widest gap is printed and not compared: it swings from seed to
    # seed and saturates for the control (PERF.md)
    print(f"[bench] served tokens checked: {gaps.size}, widest gap "
          f"{gaps.max()!r}", flush=True)
    checks = [Check("served_gap_mean", float(gaps.mean()),
                    tr["limits"]["served_gap_mean"])]
    facts = {
        "memory_peak_bytes": peak,
        "kind": "serve",
        "spans": ("batch_admit", "prefill", "decode", "host_read"),
        "step_program": "decode_fn",
        "slots": slots,
        # the traced window is one batch: step i (0-based) attends over
        # i + 1 positions of each slot
        "steps_traced": steps_per_batch,
    }
    return Outcome(len(batches) * slots, 0, metrics, checks, facts)


def batch_requests(run, b: int, slots: int, vocab: int):
    """The ``b``-th batch of the seed's requests."""
    return loadgen.requests(run.traffic, (run.seed, b), slots, vocab)


def check_sample(run, pool):
    """The requests compared with the reference, drawn from the seed."""
    rng = np.random.default_rng((run.seed, 1))
    k = min(run.traffic["check_requests"], len(pool))
    return [pool[i] for i in sorted(rng.choice(len(pool), k, replace=False))]


def reference_logits(run, params, sample, plen: int, olen: int, fp8=False):
    """The reference's logits (requests, olen, vocab) at the positions
    where the sampled requests' tokens were served: over each prompt and
    its served tokens but the last."""
    tokens = np.stack([np.concatenate([r.prompt, toks[:-1]])
                       for r, toks in sample])
    return run.reference().served_logits(run.config, params, tokens,
                                         plen - 1, olen, fp8)


def token_gaps(logits, tokens) -> np.ndarray:
    """best logit - logit of the given token, at each position."""
    import jax.numpy as jnp
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.asarray(np.asarray(tokens))[
        ..., None], axis=-1)[..., 0]
    return np.asarray(best - got, np.float64)
