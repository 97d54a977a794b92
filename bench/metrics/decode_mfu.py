"""The serving step's FLOPs over the traced batch's wall time, as a share
of the chip's bf16 peak: 2 per active parameter per token stepped (prefill
steps count) and attention over the live positions (``bench/work.py``)."""
from bench import work

UNIT = "%"


def read(r):
    if r.summary is None or r.facts.get("kind") != "serve":
        return None
    f = r.facts
    flops = sum(work.decode_step_work(r.config, f["slots"],
                                      f["slots"] * (i + 1))[0]
                for i in range(f["steps_traced"]))
    return 100.0 * flops / r.summary.window_s / r.peaks["bf16_flops_per_s"]
