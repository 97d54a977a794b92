"""Share of the traced serving batch's wall time in which no operation ran
on the chip."""
UNIT = "%"


def read(r):
    if r.summary is None or r.facts.get("kind") != "serve":
        return None
    return 100.0 * (1.0 - r.summary.busy_s / r.summary.window_s)
