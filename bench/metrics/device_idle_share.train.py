"""Share of the traced training steps' wall time in which no operation ran
on a chip, averaged over the chips."""
UNIT = "%"


def read(r):
    if r.summary is None or r.facts.get("kind") != "train":
        return None
    return 100.0 * (1.0 - r.summary.busy_s / r.summary.window_s)
