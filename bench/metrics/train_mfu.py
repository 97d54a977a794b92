"""Training FLOPs over the traced steps' wall time, as a share of the bf16
peak of all chips: 6 per active parameter per token and causal attention
(``bench/work.py``); recomputation does not count."""
UNIT = "%"


def read(r):
    if r.summary is None or r.facts.get("kind") != "train":
        return None
    f = r.facts
    flops = f["flops_per_step"] * f["steps_traced"]
    return 100.0 * flops / r.summary.window_s / (
        f["chips"] * r.peaks["bf16_flops_per_s"])
