"""The span tree (madsim_tpu/telemetry): parent links, self time, counts
on spans, the profiler annotation, and the spans and phases the sweep's
layers carry — run_batch's front door, the violation microscope
(`trace_seed`) and the engine step's named phases."""

import glob
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import madsim_tpu.telemetry as telemetry


@pytest.fixture(autouse=True)
def _telemetry_reset():
    telemetry.disable()
    yield
    telemetry.disable()


def _by_label(recs):
    out = {}
    for r in recs:
        out.setdefault(r.label, []).append(r)
    return out


# ------------------------------------------------------------------ the tree


def test_parent_links_and_self_time_across_nesting_and_threads():
    telemetry.enable()

    def worker():
        with telemetry.span("slice", site="t1"):
            with telemetry.span("dispatch", site="t1"):
                time.sleep(0.002)

    with telemetry.span("root", site="main"):
        with telemetry.span("a", site="main"):
            with telemetry.span("a1", site="main"):
                time.sleep(0.002)
        t = threading.Thread(target=worker, name="other")
        t.start()
        t.join()
        with telemetry.span("b", site="main"):
            time.sleep(0.001)
    by = {r.label: r for r in telemetry.spans()}
    root = by["root[main]"]
    assert root.parent_id is None
    assert by["a[main]"].parent_id == root.span_id
    assert by["a1[main]"].parent_id == by["a[main]"].span_id
    assert by["b[main]"].parent_id == root.span_id
    # a span opened on another thread is not a child of this thread's span
    assert by["slice[t1]"].parent_id is None
    assert by["dispatch[t1]"].parent_id == by["slice[t1]"].span_id
    assert len({r.span_id for r in by.values()}) == len(by)
    # self time: the root minus its children a and b (a1 is a's)
    kids = by["a[main]"].dur_s + by["b[main]"].dur_s
    assert telemetry.self_s(root, list(by.values())) == \
        pytest.approx(root.dur_s - kids)
    assert telemetry.self_s(by["a1[main]"], list(by.values())) == \
        by["a1[main]"].dur_s


def test_self_time_counts_overlapping_children_once():
    rec = telemetry.SpanRecord
    parent = rec("p", 0.0, 10.0, "t", {}, span_id=1)
    kids = [rec("c", 1.0, 3.0, "t", {}, span_id=2, parent_id=1),   # 1-4
            rec("c", 2.0, 4.0, "t", {}, span_id=3, parent_id=1),   # 2-6
            rec("c", 9.0, 5.0, "t", {}, span_id=4, parent_id=1),   # 9-10
            rec("g", 7.0, 1.0, "t", {}, span_id=5, parent_id=4)]   # not a child
    assert telemetry.self_s(parent, [parent] + kids) == pytest.approx(10 - 6)


def test_set_records_counts_on_the_live_span_and_is_a_noop_when_off():
    before = telemetry.spans()  # records kept from before stay readable
    off = telemetry.span("fetch", site="trace")
    with off as sp:
        sp.set(bytes=10)  # the shared no-op takes it and keeps nothing
    assert telemetry.spans() == before
    telemetry.enable()
    with telemetry.span("fetch", site="trace") as sp:
        sp.set(bytes=123, steps=4)
        sp.set(events=9)
    (r,) = telemetry.spans()
    assert r.labels == {"site": "trace", "bytes": 123, "steps": 4,
                        "events": 9}


def test_jsonl_span_events_carry_id_and_parent_and_old_lines_parse(tmp_path):
    telemetry.enable(out_dir=str(tmp_path))
    with telemetry.span("outer", site="x"):
        with telemetry.span("inner", site="x"):
            pass
    telemetry.disable()
    spans = [e for e in telemetry.read_events(str(tmp_path / "events.jsonl"))
             if e["kind"] == "span"]
    by = {e["name"]: e for e in spans}
    assert by["outer"]["parent"] is None
    assert by["inner"]["parent"] == by["outer"]["id"]
    assert json.loads(json.dumps(by["inner"])) == by["inner"]
    # a line written before the tree existed still validates
    old = {"format": telemetry.TELEMETRY_FORMAT, "kind": "span",
           "name": "dispatch", "t0_s": 0.1, "dur_s": 0.2,
           "labels": {"site": "run_batch"}, "seq": 0, "thread": "MainThread"}
    doc = telemetry.parse_event(json.dumps(old))
    assert "id" not in doc and doc["dur_s"] == 0.2


def test_span_seconds_is_labelled_by_span_and_site():
    reg = telemetry.enable()
    for site in ("run_batch", "shrink", "run_batch"):
        with telemetry.span("dispatch", site=site):
            pass
    with telemetry.span("decode", site="run_batch"):
        pass
    h = reg.histogram("span_seconds")
    assert h.snapshot(span="dispatch", site="run_batch")["count"] == 2
    assert h.snapshot(span="dispatch", site="shrink")["count"] == 1
    assert h.snapshot(span="decode", site="run_batch")["count"] == 1
    assert h.snapshot(site="dispatch") is None


def test_annotations_only_while_enabled(monkeypatch):
    """Disabled: the shared no-op and no TraceAnnotation at all. Enabled:
    one `name[site]` annotation per span."""
    import jax.profiler

    made = []

    class Spy:
        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    a = telemetry.span("dispatch", site="run_batch")
    assert a is telemetry.span("decode")
    with a:
        pass
    assert made == []
    telemetry.enable()
    with telemetry.span("dispatch", site="run_batch"):
        with telemetry.span("plain"):
            pass
    assert made == ["dispatch[run_batch]", "plain"]


# ------------------------------------------------------------ the front door


def _kv_lane_check_workload():
    import dataclasses

    from madsim_tpu.tpu.kv import kv_workload

    wl = kv_workload(virtual_secs=0.5)
    assert wl.lane_check is not None
    return dataclasses.replace(wl, lane_check_sample=2)


def test_run_batch_spans_form_the_front_door_tree():
    from madsim_tpu.tpu.batch import run_batch

    wl = _kv_lane_check_workload()
    # two chunks, and segments short enough that run_state waits on the
    # early-stop reduction
    run_batch(range(8), wl, chunk=4, mesh=None, dispatch_steps=64,
              max_traces=0)  # compile outside the captured call
    telemetry.enable()
    run_batch(range(8), wl, chunk=4, mesh=None, dispatch_steps=64,
              max_traces=0)
    recs = telemetry.spans()
    by = _by_label(recs)
    ids = {r.span_id: r for r in recs}
    (root,) = by["run_batch[chunked]"]
    assert root.parent_id is None
    for label in ("dispatch[run_batch]", "decode[run_batch]"):
        assert len(by[label]) == 2
        assert all(r.parent_id == root.span_id for r in by[label])
    assert by["wait[segment]"]
    assert all(ids[r.parent_id].label == "dispatch[run_batch]"
               for r in by["wait[segment]"])
    for label in ("wait[run_batch]", "lane_check[run_batch]"):
        assert len(by[label]) == 2
        assert all(ids[r.parent_id].label == "decode[run_batch]"
                   for r in by[label])
    # every span of the call lies inside the root
    for r in recs:
        assert root.t0_s <= r.t0_s and \
            r.t0_s + r.dur_s <= root.t0_s + root.dur_s + 1e-9


def test_run_batch_shrink_and_trace_spans_hang_off_the_root(monkeypatch):
    import dataclasses

    from madsim_tpu import triage
    from madsim_tpu.tpu.batch import run_batch
    from tests.test_triage import _sched_workload

    wl = dataclasses.replace(_sched_workload(), max_steps=3_000)
    shrunk = []

    def fake_shrink(workload, seed, **kw):
        # the bundle itself is triage's business (tests/test_triage.py)
        with telemetry.span("dispatch", site="shrink"):
            shrunk.append(seed)
        return type("SR", (), {"bundle": None, "bundle_path": None})()

    monkeypatch.setattr(triage, "shrink_seed", fake_shrink)
    telemetry.enable()
    r = run_batch(range(24), wl, mesh=None, shrink_on_violation=True,
                  max_traces=1, repro_on_host=False)
    assert r.violations and shrunk == r.violating_seeds[:1]
    recs = telemetry.spans()
    by = _by_label(recs)
    ids = {x.span_id: x for x in recs}
    (root,) = by["run_batch[chunked]"]
    (shrink,) = by["shrink[run_batch]"]
    assert shrink.parent_id == root.span_id
    (disp,) = by["dispatch[shrink]"]
    assert disp.parent_id == shrink.span_id
    (tr,) = by["trace[run_batch]"]
    assert tr.parent_id == root.span_id
    for label in ("scan[trace]", "fetch[trace]", "extract[trace]"):
        (x,) = by[label]
        assert ids[x.parent_id] is tr


# ----------------------------------------------------------- the microscope


def test_trace_seed_matches_extract_trace_and_records_its_three_spans():
    from madsim_tpu.tpu import make_raft_spec
    from madsim_tpu.tpu.engine import BatchedSim
    from madsim_tpu.tpu.trace import extract_trace, trace_seed

    spec = make_raft_spec()
    sim = BatchedSim(spec, None)
    _, recs = sim.run_traced(3, max_steps=250)
    want = extract_trace(recs, kind_names=spec.msg_kind_names)
    telemetry.enable()
    got = trace_seed(sim, 3, max_steps=250, kind_names=spec.msg_kind_names)
    assert got == want and want
    by = {r.label: r for r in telemetry.spans()}
    assert set(by) == {"scan[trace]", "fetch[trace]", "extract[trace]"}
    host = jax.device_get(recs)
    assert by["fetch[trace]"].labels["bytes"] == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(host))
    assert by["extract[trace]"].labels["events"] == len(want)
    steps = by["extract[trace]"].labels["steps"]
    assert 0 < steps <= 250
    assert steps == len({e.step for e in want})
    assert by["scan[trace]"].labels["max_steps"] == 250
    assert 0 < by["scan[trace]"].labels["steps"] <= 250

    # a violating seed: the scan stops with the lane, short of max_steps,
    # one step after the violation's
    from test_trace import _SHORT, split_brain_spec

    telemetry.disable()
    telemetry.enable()
    got = trace_seed(BatchedSim(split_brain_spec(), _SHORT), 0,
                     max_steps=2_000)
    scan = {r.label: r for r in telemetry.spans()}["scan[trace]"].labels
    assert scan["max_steps"] == 2_000
    assert 0 < scan["steps"] < scan["max_steps"]
    violation = [e.step for e in got if e.kind == "violation"]
    assert violation == [scan["steps"] - 1]


# ------------------------------------------------------------ the step phases


def _phase_sim():
    from madsim_tpu.tpu import make_raft_spec
    from madsim_tpu.tpu.engine import BatchedSim
    from madsim_tpu.tpu.spec import SimConfig

    cfg = SimConfig(
        horizon_us=500_000, loss_rate=0.1,
        crash_interval_lo_us=100_000, crash_interval_hi_us=300_000,
        restart_delay_lo_us=50_000, restart_delay_hi_us=100_000,
        partition_interval_lo_us=100_000, partition_interval_hi_us=300_000,
        partition_heal_lo_us=50_000, partition_heal_hi_us=100_000,
    )
    return BatchedSim(make_raft_spec(n_nodes=5), cfg)


def test_the_run_program_carries_every_phase_in_its_op_metadata():
    import re

    from madsim_tpu.tpu.engine import STEP_PHASES

    sim = _phase_sim()
    st = sim.init(jnp.arange(8, dtype=jnp.uint32))
    hlo = sim._run.lower(sim, st, 4).as_text(debug_info=True)
    found = set(re.findall(r"/step/(\w+)/", hlo))
    assert set(STEP_PHASES) <= found, found


def test_phases_leave_the_step_jaxpr_unchanged(monkeypatch):
    import contextlib

    from madsim_tpu.tpu import engine
    from madsim_tpu.tpu.engine import split_state

    sim = _phase_sim()
    st = sim.init(jnp.arange(4, dtype=jnp.uint32))
    hot, cold, const = split_state(st)
    scoped = str(jax.make_jaxpr(sim._step_split)(hot, cold, const))
    monkeypatch.setattr(engine.jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = str(jax.make_jaxpr(sim._step_split)(hot, cold, const))
    assert scoped == bare


def test_a_cpu_profile_holds_the_program_spans_on_its_host_plane(tmp_path):
    from madsim_tpu.tpu import make_raft_spec
    from madsim_tpu.tpu.batch import BatchWorkload, run_batch

    wl = BatchWorkload(spec=make_raft_spec(n_nodes=3), max_steps=200)
    run_batch(range(4), wl, mesh=None)
    telemetry.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_batch(range(4), wl, mesh=None)
    finally:
        jax.profiler.stop_trace()
    spans = {r.label for r in telemetry.spans()}
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = {e.name for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}
    assert {"run_batch[chunked]", "dispatch[run_batch]", "decode[run_batch]",
            "wait[run_batch]"} <= host
    assert spans <= host
