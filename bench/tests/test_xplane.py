"""The trace reduction: on made-up intervals, and on a small trace
recorded on a TPU v5e chip by ``record_trace.py`` (kept beside this file)."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import xplane  # noqa: E402

HERE = Path(__file__).resolve().parent


def test_union_complement_intersect():
    u = xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert xplane.length(u) == 5
    assert xplane.complement(u, 0, 10) == [(3, 5), (7, 10)]
    assert xplane.intersect(u, [(2, 6)]) == [(2, 3), (5, 6)]


def test_gaps_named_by_the_span_that_overlaps_most():
    spans = [(0, 4, "prefill"), (4, 6, "host_read"), (6, 10, "decode")]
    named = xplane.name_gaps([(3, 6), (9.5, 12)], spans)
    assert named == {"host_read": 3, "decode": 2.5}


def test_leaf_ops_drop_containers():
    evs = [(0, 10, "%while.1 = (...) while(...)"), (0, 4, "%fusion.2 = x"),
           (4, 10, "%all-reduce.3 = y"), (11, 12, "%copy.4 = z")]
    assert [xplane.short(n) for *_, n in xplane.leaf_ops(evs)] == [
        "fusion.2", "all-reduce.3", "copy.4"]


def test_summary_of_two_devices():
    spans = [(0.0, 1.0, "train_step")]
    dev0 = xplane.Device(
        ops=[(0.0, 0.7, "%while.9 = (...) while(...)"),
             (0.0, 0.3, "%fusion.1 = f32[8] fusion()"),
             (0.3, 0.5, "%all-reduce.2 = f32[8] all-reduce()"),
             (0.6, 0.7, "%all-gather-done.3 = f32[8] all-gather-done()")],
        modules=[(0.0, 0.7, "jit_local_step(123)")],
        async_ops=[(0.5, 0.7, "%all-gather-start.3 = f32[8] x()")])
    dev1 = xplane.Device(
        ops=[(0.0, 0.5, "%fusion.1 = f32[8] fusion()"),
             (0.5, 0.6, "%all-reduce.2 = f32[8] all-reduce()")],
        modules=[(0.0, 0.6, "jit_local_step(123)")])
    s = xplane.summarize_devices([dev0, dev1], spans)
    assert s.window_s == 1.0 and s.devices == 2
    assert s.busy_s == pytest.approx((0.7 + 0.6) / 2)
    # dev0: collectives 0.3-0.7 (the transfer 0.5-0.7 included), the core
    # blocked on them 0.3-0.5 and 0.6-0.7; dev1: 0.5-0.6
    assert s.collective_s == pytest.approx((0.4 + 0.1) / 2)
    assert s.exposed_collective_s == pytest.approx((0.3 + 0.1) / 2)
    assert s.program("local_step") == [pytest.approx(0.65)]
    assert s.idle_by_span == [["train_step", pytest.approx(0.35)]]
    assert s.top_ops[0] == ["fusion.1", pytest.approx(0.4)]


@pytest.mark.skipif(not (HERE / "probe.xplane.pb").exists(),
                    reason="no recorded chip trace beside the test")
def test_recorded_chip_trace():
    meta = json.loads((HERE / "probe.json").read_text())
    names = ("prefill", "host_read")
    devices, spans = xplane.load(HERE / "probe.xplane.pb", names)
    assert len(devices) == 1
    assert sorted({n for *_, n in spans}) == ["host_read", "prefill"]
    s = xplane.summarize(HERE / "probe.xplane.pb", names)
    runs = s.program(meta["program"])
    assert len(runs) == meta["calls"]
    # each call's ops lie inside its program's execution
    assert s.busy_s <= sum(runs) * 1.001
    assert s.busy_s > 0
    # the sleeps are idle time, put on the host_read spans
    idle = dict(s.idle_by_span)
    assert idle["host_read"] >= meta["calls"] * meta["sleep_s"] * 0.9
    assert s.window_s == pytest.approx(s.busy_s + sum(idle.values()))
