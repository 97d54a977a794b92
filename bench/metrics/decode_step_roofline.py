"""The serving step program's share of its roofline: the least time the
chip needs for the work of the traced steps (the larger of FLOPs over the
bf16 peak and bytes over the HBM bandwidth, step by step, from
``bench/work.py``), over the device time of those executions. The work
counts each parameter once at bf16, the live KV positions, one new KV
position per token and the logits; nothing the implementation adds."""
from bench import work

UNIT = "%"


def bound(r) -> tuple[float, str]:
    """(least seconds, what bounds most steps)."""
    f, p = r.facts, r.peaks
    least, by_bytes = 0.0, 0
    for i in range(f["steps_traced"]):
        fl, by = work.decode_step_work(r.config, f["slots"],
                                       f["slots"] * (i + 1))
        t_f, t_b = fl / p["bf16_flops_per_s"], by / p["hbm_bytes_per_s"]
        least += max(t_f, t_b)
        by_bytes += t_b >= t_f
    return least, "bytes" if 2 * by_bytes >= f["steps_traced"] else "flops"


def note(r) -> str:
    return f"bound by {bound(r)[1]}"


def read(r):
    if r.summary is None or r.facts.get("kind") != "serve":
        return None
    durs = r.summary.program(r.facts["step_program"])
    if not durs:
        return None
    least, _ = bound(r)
    # where the trace holds another number of executions than steps,
    # compare per step
    least *= len(durs) / r.facts["steps_traced"]
    return 100.0 * least / sum(durs)
