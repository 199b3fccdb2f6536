"""The trace reduction (benchmark/lib/trace.py) on small committed traces:
busy/idle union arithmetic, op totals by name, the collective share and
the idle gaps labelled by the host span around them."""

import json
import os

import pytest

from benchmark.lib import trace

DATA = os.path.join(os.path.dirname(__file__), "data")

# two devices, ns; device 0 has nested and overlapping ops
SYNTH = {
    "devices": {
        "/device:TPU:0": {
            "ops": [["fusion.1", 100, 200],      # 100-300
                    ["fusion.2", 250, 100],      # 250-350, overlaps
                    ["all-reduce.3", 500, 100],  # 500-600
                    ["fusion.1", 900, 200]],     # 900-1100, clipped at 1000
            "modules": [["jit__run(7)", 100, 500],
                        ["jit__run_traced(8)", 900, 100],
                        ["jit__any_alive(9)", 600, 10]],
        },
        "/device:TPU:1": {
            "ops": [["fusion.1", 0, 1000]],
            "modules": [["jit__run(7)", 0, 1000]],
        },
    },
    "host": [["bench.traced.start", 0, 0], ["bench.call", 0, 1000],
             ["decode[run_batch]", 650, 200], ["bench.traced.end", 1000, 0]],
}


def test_busy_is_the_union_of_program_and_op_intervals_in_the_window():
    s = trace.reduce(SYNTH, 0, 1000)
    d0 = s.devices["/device:TPU:0"]
    assert d0.busy_ns == 510 + 100  # 100-610 (ops and programs), 900-1000
    assert d0.idle == [(0, 100), (610, 900)]
    assert s.idle_share() == pytest.approx(1 - 610 / 1000)
    assert s.busy_s() == pytest.approx((610 + 1000) / 2 / 1e9)


def test_op_and_program_totals_by_name():
    s = trace.reduce(SYNTH, 0, 1000)
    d0 = s.devices["/device:TPU:0"]
    assert d0.op_ns == {"fusion.1": 300, "fusion.2": 100, "all-reduce.3": 100}
    assert s.top_ops(2)[0] == ("fusion.1", pytest.approx(1300 / 2 / 1e9))
    # `_run` programs on both devices (not `_run_traced`), clipped at 1000
    assert s.program_ns("_run") == 500 + 1000
    assert s.program_ns("_run_traced") == 100
    assert s.program_ns("_init") == 0
    while_op = {"devices": {"/device:TPU:0": {
        "ops": [["while.1", 0, 100], ["fusion.1", 0, 10]], "modules": []}},
        "host": []}
    assert trace.reduce(while_op, 0, 100).top_ops() == [
        ("fusion.1", pytest.approx(10 / 1e9))]


def test_collective_share_of_the_busiest_device():
    s = trace.reduce(SYNTH, 0, 1000)
    assert s.collective_share() is None  # device 1 is busiest, no collective
    s = trace.reduce({"devices": {"/device:TPU:0":
                                  SYNTH["devices"]["/device:TPU:0"]},
                      "host": []}, 0, 1000)
    assert s.collective_share() == pytest.approx(100 / 610)


def test_idle_gaps_go_to_the_innermost_host_span():
    s = trace.reduce(SYNTH, 0, 1000)
    labels = dict(trace.label_idle(s, SYNTH["host"]))
    assert labels == {"bench.call": pytest.approx(100 / 1e9),
                      "decode[run_batch]": pytest.approx(290 / 1e9)}
    assert trace.window_of(SYNTH, "bench.traced.start",
                           "bench.traced.end") == (0, 1000)


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []}, 0, 1)


def _brute(intervals, lo, hi):
    """Busy ns by a 1-ns grid: the union as a mask, no interval logic."""
    import numpy as np

    mask = np.zeros(int(hi - lo), bool)
    for a, b in intervals:
        a, b = max(int(a - lo), 0), min(int(b - lo), mask.size)
        if b > a:
            mask[a:b] = True
    return int(mask.sum())


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith(".json")))
def test_reduction_of_a_recorded_v5e_excerpt(name):
    """An excerpt of a real v5e trace recorded in PR 22 (the schema
    `trace.load` produces): the reduction agrees with a brute-force mask
    and with direct sums."""
    with open(os.path.join(DATA, name)) as f:
        ex = json.load(f)
    lo, hi = (int(x) for x in ex["window"])
    s = trace.reduce(ex, lo, hi)
    for plane, dev in ex["devices"].items():
        d = s.devices[plane]
        spans = [(st, st + du) for _, st, du in dev["ops"] + dev["modules"]]
        assert d.busy_ns == pytest.approx(_brute(spans, lo, hi), abs=len(spans))
        assert sum(b - a for a, b in d.idle) == pytest.approx(
            (hi - lo) - d.busy_ns)
        for op in {n for n, _, _ in dev["ops"]}:
            direct = sum(min(st + du, hi) - max(st, lo)
                         for n, st, du in dev["ops"]
                         if n == op and st < hi and st + du > lo)
            assert d.op_ns.get(op, 0.0) == pytest.approx(direct)
        coll = [(st, st + du) for n, st, du in dev["ops"]
                if trace.COLLECTIVE.search(n)]
        assert d.collective_ns == pytest.approx(_brute(coll, lo, hi),
                                                abs=len(coll) + 1)
    assert 0.0 <= s.idle_share() <= 1.0
