"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

The trace's device planes (``/device:TPU:<n>``) carry a line of XLA
operations (one after another on the core; a loop or call is an event
that spans the operations it runs), a line of asynchronous operations
(a collective's transfer, from its start to its done), and a line of XLA
modules (a module is one execution of a jitted program); the host plane
carries the driver's ``TraceAnnotation`` spans, whose names the driver
gives. Both are on one clock, to within a millisecond. Within the traced
window, for each device:

- busy: the union of the intervals in which an operation ran;
- idle gaps: the rest of the window, each named by the driver span that
  overlaps it most (``other`` where none does);
- module time: the durations of each program's executions;
- collective time (transfers included), and the part of it in which the
  core ran the collective's own operations and nothing else (exposed).

Device figures are averaged over the devices of the trace.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|psum", re.IGNORECASE)


# ------------------------------------------------------------- intervals

def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def complement(merged, lo: float, hi: float):
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def short(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaf_ops(events):
    """The operations that contain no other: on a core operations run
    one after another, so an event that overlaps the next one spans it (a
    loop, a call)."""
    evs = sorted(events, key=lambda e: (e[0], -(e[1] - e[0])))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1][0] >= e[1]]


def name_gaps(gaps, spans) -> dict[str, float]:
    """Seconds of idle gaps by the host span that overlaps each most."""
    spans = sorted(spans)
    out: dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        best, who = 0.0, "other"
        for s, e, name in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, who = ov, name
        out[who] += ge - gs
    return dict(out)


# ------------------------------------------------------------- summary

@dataclass
class Device:
    ops: list                 # (start, end, name), seconds
    modules: list             # (start, end, name), seconds
    async_ops: list = ()      # (start, end, name), seconds


@dataclass
class Summary:
    window_s: float
    busy_s: float
    idle_by_span: list        # [[span, seconds]], most first
    top_ops: list             # [[op, seconds]], most first
    module_s: dict            # program name -> [durations] (device mean)
    collective_s: float
    exposed_collective_s: float
    devices: int = 0

    def program(self, fragment: str) -> list[float]:
        """Durations of the executions of every program whose name
        contains ``fragment``."""
        out = []
        for name, durs in self.module_s.items():
            if fragment in name:
                out.extend(durs)
        return out


def summarize_devices(devices: list[Device], spans) -> Summary:
    """Reduce per-device events to the traced window: the trace is taken
    around the traced work alone, so the window runs from the first event
    of the driver's spans and the device's programs to the last (the
    device clock can sit a millisecond off the host's)."""
    bounds = list(spans) + [m for d in devices for m in d.modules]
    lo = min(s for s, _, _ in bounds)
    hi = max(e for _, e, _ in bounds)
    n = len(devices)
    busy = coll = exposed = 0.0
    idle = defaultdict(float)
    ops = defaultdict(float)
    runs = []                 # per device: program name -> durations
    for d in devices:
        evs = [(max(s, lo), min(e, hi), nm) for s, e, nm in d.ops
               if e > lo and s < hi]
        merged = union((s, e) for s, e, _ in evs)
        busy += length(merged)
        for name, sec in name_gaps(complement(merged, lo, hi),
                                   spans).items():
            idle[name] += sec / n
        leaves = leaf_ops(evs)
        for s, e, nm in leaves:
            ops[short(nm)] += (e - s) / n
        mine = union((s, e) for s, e, nm in leaves if COLLECTIVE.search(nm))
        other = union((s, e) for s, e, nm in leaves
                      if not COLLECTIVE.search(nm))
        moving = [(max(s, lo), min(e, hi)) for s, e, nm in d.async_ops
                  if COLLECTIVE.search(nm) and e > lo and s < hi]
        coll += length(union(list(mine) + moving))
        exposed += length(mine) - length(intersect(mine, other))
        mods = defaultdict(list)
        for s, e, nm in d.modules:
            if s >= lo and e <= hi:
                mods[nm].append(e - s)
        runs.append(mods)
    # each execution's duration averaged over the devices where they
    # agree in number; pooled otherwise
    module_s = {}
    for nm in set().union(*runs):
        lists = [r[nm] for r in runs]
        if len({len(x) for x in lists}) == 1:
            module_s[nm] = [sum(x) / n for x in zip(*lists)]
        else:
            module_s[nm] = [v for x in lists for v in x]
    return Summary(
        window_s=hi - lo, busy_s=busy / n,
        idle_by_span=sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
        top_ops=sorted(([k, v] for k, v in ops.items()),
                       key=lambda kv: -kv[1])[:10],
        module_s=module_s, collective_s=coll / n,
        exposed_collective_s=exposed / n, devices=n)


def find(trace_dir) -> Path:
    paths = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path, span_names):
    """(devices, the host spans named ``span_names``) of a trace file,
    times in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, spans = [], []
    names = set(span_names)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(e.start_ns * 1e-9, e.end_ns * 1e-9,
                                  e.name) for e in line.events]
                     for line in plane.lines}
            devices.append(Device(lines.get(OPS_LINE, []),
                                  lines.get(MODULES_LINE, []),
                                  lines.get(ASYNC_LINE, [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        spans.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                      e.name))
    return devices, spans


def summarize(path, span_names) -> Summary | None:
    """The summary of a trace file, or None where it holds no device or
    none of the driver's spans (a run off the chip)."""
    devices, spans = load(path, span_names)
    if not devices or not spans:
        return None
    return summarize_devices(devices, spans)


@dataclass
class Reading:
    """What a per-layer metric reader gets."""
    summary: Summary | None
    facts: dict
    peaks: dict
    config: dict
    traffic: dict
