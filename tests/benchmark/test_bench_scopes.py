"""The readers of the program's own spans and step phases
(benchmark/lib/scopes.py and the metrics that use it), on synthetic runs:
each reads its number from the spans or ops it names, and None where a
program records none of them."""

import types

import pytest

from benchmark.lib import harness, scopes, trace

SPAN_METRICS = ("host_self_ms", "lane_check_ms", "trace_scan_ms",
                "trace_fetch_ms", "trace_extract_ms")
PHASE_METRICS = tuple(f"step_{p}_us" for p in scopes.PHASES) + (
    "step_unscoped_us",)


def _span(name, site, t0, dur, sid, parent=None, **labels):
    return types.SimpleNamespace(name=name, t0_s=t0, dur_s=dur,
                                 labels={"site": site, **labels},
                                 span_id=sid, parent_id=parent)


def _run(spans=(), records=(), trace_summary=None, name="x"):
    cell = types.SimpleNamespace(name=name, traffic={"trace_calls": 1})
    return harness.Run(cell=cell, records=list(records), spans=list(spans),
                       trace=trace_summary)


def _reader(name):
    return harness.load_module(f"{harness.BENCH_DIR}/metrics/{name}.py",
                               "t_" + name)


# one chunked call: 10 s, of which the waits cover 7 s and the oracle 1 s
CALL = [
    _span("run_batch", "chunked", 0.0, 10.0, 1),
    _span("dispatch", "run_batch", 0.0, 6.0, 2, 1),
    _span("wait", "segment", 0.5, 5.0, 3, 2),
    _span("decode", "run_batch", 6.0, 3.5, 4, 1),
    _span("wait", "run_batch", 6.0, 2.0, 5, 4),
    _span("lane_check", "run_batch", 8.0, 1.0, 6, 4),
    # a wait on another call's tree does not count against this one
    _span("wait", "segment", 20.0, 1.0, 7, None),
]


def test_host_self_time_subtracts_waits_and_the_oracle():
    run = _run(CALL)
    assert _reader("host_self_ms").read(run) == pytest.approx(1e3 * 2.0)
    second = [_span("run_batch", "chunked", 30.0, 4.0, 11),
              _span("wait", "run_batch", 31.0, 3.5, 12, 11)]
    run = _run(CALL + second)
    # the second call's wait runs past its end: only 3 s of it count
    assert _reader("host_self_ms").read(run) == pytest.approx(
        1e3 * (2.0 + 1.0) / 2)
    assert scopes.host_self_ms(run, "run_batch[chunked]", ("wait",)) <= \
        1e3 * 10.0


def test_lane_check_is_the_mean_of_its_spans():
    run = _run(CALL + [_span("lane_check", "run_batch", 9.0, 0.5, 8, 4)])
    assert _reader("lane_check_ms").read(run) == pytest.approx(750.0)


def test_microscope_spans_per_bundle():
    spans = [_span("trace", "run_batch", 0.0, 9.0, 1),
             _span("scan", "trace", 0.0, 8.0, 2, 1),
             _span("fetch", "trace", 8.0, 0.5, 3, 1, bytes=100),
             _span("extract", "trace", 8.5, 0.25, 4, 1, steps=3, events=5)]
    spans += [_span(s.name, s.labels["site"], s.t0_s + 10, s.dur_s,
                    s.span_id + 10, s.parent_id and s.parent_id + 10)
              for s in spans]
    run = _run(spans, records=[{"bundle": "a"}, {"bundle": None}])
    assert _reader("trace_scan_ms").read(run) == pytest.approx(16_000.0)
    assert _reader("trace_fetch_ms").read(run) == pytest.approx(1_000.0)
    assert _reader("trace_extract_ms").read(run) == pytest.approx(500.0)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_readers_read_none_without_their_spans(name):
    # a program that records only the older spans (no tree, no children)
    old = [types.SimpleNamespace(name="dispatch", t0_s=0.0, dur_s=1.0,
                                 labels={"site": "run_batch"}),
           types.SimpleNamespace(name="trace", t0_s=1.0, dur_s=1.0,
                                 labels={"site": "run_batch", "seed": 3})]
    assert _reader(name).read(_run(old, records=[{"bundle": "a"}])) is None
    assert _reader(name).read(_run()) is None


# ------------------------------------------------------------------- phases

HLO = """\
ENTRY %main.9 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.1, metadata={op_name="jit(_run)/while/body/step/handlers/add" source_file="e.py"}
  %select_fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fc.2, metadata={op_name="jit(_run)/while/body/step/network/select_n"}
  %copy.3 = s32[8]{0} copy(%select_fusion.2)
  ROOT %fusion.4 = s32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fc.4, metadata={op_name="jit(_run)/while/body/step/select"}
}
"""

EVENTS = {
    "devices": {"/device:TPU:0": {
        "ops": [["while.1", 100, 900],
                ["fusion.1", 100, 300],         # handlers
                ["select_fusion.2", 400, 200],  # network
                ["copy.3", 600, 100],           # no phase
                ["fusion.4", 700, 100],         # select
                ["fusion.1", 1500, 50]],        # the same name, another program
        "modules": [["jit__run(7)", 100, 900], ["jit__init(3)", 1500, 50]]}},
    "host": [["bench.traced.start", 0, 0], ["bench.traced.end", 2000, 0]],
}


def test_instruction_phases_from_the_program_text():
    ph = scopes.hlo_phases(HLO)
    assert ph["fusion.1"] == "handlers" and ph["select_fusion.2"] == "network"
    assert ph["fusion.4"] == "select"
    assert ph["copy.3"] is None and ph["p"] is None


def test_phase_time_counts_only_the_run_program_s_ops():
    ns = scopes.phase_ns(EVENTS, 0, 2000, scopes.hlo_phases(HLO))
    assert ns == {"select": 100, "handlers": 300, "chaos": 0, "network": 200,
                  "invariants": 0, "finish": 0, "unscoped": 100}
    # clipped to the window like the program time
    assert scopes.phase_ns(EVENTS, 0, 500, scopes.hlo_phases(HLO))[
        "network"] == 100
    # a program without phases reads nothing
    assert scopes.phase_ns(EVENTS, 0, 2000, {"fusion.1": None}) is None


def _traced_run(monkeypatch, events, hlo):
    monkeypatch.setattr(scopes, "_CACHE", {})
    monkeypatch.setattr(scopes, "xplane_path", lambda run: "p.xplane.pb")
    monkeypatch.setattr(trace, "load", lambda path: events)
    monkeypatch.setattr(scopes, "run_program_text", lambda run: hlo)
    run = _run(records=[{"loop_steps": 4}, {"loop_steps": 99}],
               trace_summary=trace.reduce(events, 0, 2000))
    return run


def test_step_phase_readers_divide_by_the_traced_iterations(monkeypatch):
    run = _traced_run(monkeypatch, EVENTS, HLO)
    us = {m: _reader(m).read(run) for m in PHASE_METRICS}
    assert us["step_handlers_us"] == pytest.approx(300 / 4 / 1e3)
    assert us["step_unscoped_us"] == pytest.approx(100 / 4 / 1e3)
    assert us["step_chaos_us"] == 0.0
    # with `engine_step_us` over the same iterations, the parts add up
    whole = _reader("engine_step_us").read(run)
    assert sum(us.values()) == pytest.approx(whole * 700 / 900)


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_readers_read_none_without_phases_or_a_profile(name,
                                                             monkeypatch):
    run = _traced_run(monkeypatch, EVENTS, HLO.replace("/step/", "/"))
    assert _reader(name).read(run) is None
    run.trace = None
    assert _reader(name).read(run) is None
    two = {"devices": {"/device:TPU:0": {
        "ops": EVENTS["devices"]["/device:TPU:0"]["ops"],
        "modules": [["jit__run(7)", 100, 400], ["jit__run(8)", 500, 500]]}},
        "host": EVENTS["host"]}
    assert _reader(name).read(_traced_run(monkeypatch, two, HLO)) is None


def test_the_raft5_run_program_names_every_phase():
    """The program text the phase readers map from, compiled here for a
    small chunk of the raft5 configuration."""
    cell = harness.load_cell("raft5.sweep",
                             traffic_override={"seeds_per_call": 16})
    run = harness.Run(cell=cell)
    ph = scopes.hlo_phases(scopes.run_program_text(run))
    assert set(scopes.PHASES) <= set(ph.values())
