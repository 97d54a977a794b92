"""Device time of one execution of the serving step program, in ms: the
mean duration of its executions in the traced batch."""
UNIT = "ms"


def read(r):
    if r.summary is None or r.facts.get("kind") != "serve":
        return None
    durs = r.summary.program(r.facts["step_program"])
    return 1e3 * sum(durs) / len(durs) if durs else None
