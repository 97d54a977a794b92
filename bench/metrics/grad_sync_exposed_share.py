"""Collective time during which no other operation ran on the chip, over
the device time of the train step program, averaged over the chips."""
UNIT = "%"


def read(r):
    if r.summary is None or r.facts.get("kind") != "train":
        return None
    step = sum(r.summary.program(r.facts["step_program"]))
    if step <= 0 or r.summary.collective_s <= 0:
        return None
    return 100.0 * r.summary.exposed_collective_s / step
