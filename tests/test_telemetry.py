"""Telemetry (madsim_tpu/telemetry): the observe-only contract, pinned.

The subsystem's promises (docs/observability.md):
  * **observe-only, bit-exact**: explorer fingerprints and the canonical
    golden trajectory digest are IDENTICAL with telemetry enabled vs
    disabled — capture happens at decode/host boundaries, never inside
    jitted code;
  * **one schema**: every event on the JSONL sink validates against
    ``madsim-tpu-telemetry/1`` and round-trips; the nemesis per-occurrence
    rows serialize in stable key/row order (docs/nemesis.md);
  * **timelines are faithful**: the Perfetto export of a violating replay
    matches the `format_trace` text event-for-event (every TraceEvent has
    exactly one anchor track/flow/instant event), and is well-formed
    Chrome-trace JSON;
  * **the farm is scrapeable**: `campaign serve` maintains status.json +
    a Prometheus textfile atomically — a concurrent reader never sees a
    torn file;
  * **near-free**: the span-wrapped dispatch loop costs <2% over bare
    (bench.bench_telemetry_overhead).

`make telemetry-smoke` runs this WHOLE file (including the slow-marked
bit-identity/repro/overhead tests, which the tier-1 wall budget keeps out
of the default `-m 'not slow'` run).
"""

import dataclasses
import json
import os
import threading
import time

import numpy as np
import pytest

import madsim_tpu.telemetry as telemetry

from tests.test_triage import _sched_workload


@pytest.fixture(autouse=True)
def _telemetry_reset():
    """Telemetry state is process-global: never leak an enable."""
    telemetry.disable()
    yield
    telemetry.disable()


# ------------------------------------------------------------ event schema


def test_event_schema_roundtrip(tmp_path):
    """Every sink line validates against madsim-tpu-telemetry/1 and
    round-trips through JSON unchanged."""
    reg = telemetry.enable(out_dir=str(tmp_path))
    reg.counter("sweep_violations", "v").inc(3, workload="raft")
    reg.gauge("sweep_occupancy", "o").set(0.97, device=0)
    reg.histogram("span_seconds").observe(0.02, site="dispatch")
    with telemetry.span("dispatch", site="test"):
        pass
    telemetry.disable()

    path = tmp_path / "events.jsonl"
    events = telemetry.read_events(str(path))  # parse_event on every line
    assert [e["kind"] for e in events] == [
        "counter", "gauge", "histogram", "histogram", "span",
    ]
    # seq is a gapless monotone cursor
    assert [e["seq"] for e in events] == list(range(len(events)))
    for e in events:
        assert e["format"] == telemetry.TELEMETRY_FORMAT
        # byte-level round trip: parse(dump(parse(line))) is identity
        assert telemetry.parse_event(json.dumps(e)) == e
    c = events[0]
    assert (c["name"], c["value"], c["labels"]) == (
        "sweep_violations", 3, {"workload": "raft"},
    )
    sp = events[-1]
    assert sp["labels"] == {"site": "test"} and sp["dur_s"] >= 0


def test_event_schema_rejects_malformed():
    ok = {
        "format": telemetry.TELEMETRY_FORMAT, "kind": "counter",
        "name": "x", "value": 1, "labels": {}, "seq": 0,
    }
    telemetry.parse_event(json.dumps(ok))
    for breakage in (
        {"format": "bogus/9"},
        {"kind": "summary"},
        {"value": None, "kind": "span"},  # span needs t0_s/dur_s
        {"labels": [1, 2]},
    ):
        bad = {**ok, **breakage}
        with pytest.raises(ValueError):
            telemetry.parse_event(json.dumps(bad))
    with pytest.raises(ValueError):
        telemetry.parse_event("[1, 2]")


def test_registry_prom_exposition():
    reg = telemetry.MetricsRegistry()
    reg.counter("sweep_violations", "violations").inc(2, workload="raft")
    reg.counter("sweep_violations").inc(1, workload="kv")
    reg.gauge("farm_queue_depth").set(4)
    reg.histogram("span_seconds", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.to_prom()
    assert 'madsim_sweep_violations_total{workload="raft"} 2' in text
    assert 'madsim_sweep_violations_total{workload="kv"} 1' in text
    assert "madsim_farm_queue_depth 4" in text
    assert 'madsim_span_seconds_bucket{le="0.1"} 0' in text
    assert 'madsim_span_seconds_bucket{le="1.0"} 1' in text
    assert 'madsim_span_seconds_bucket{le="+Inf"} 1' in text
    assert "madsim_span_seconds_count 1" in text
    # same name, different kind: loud error, never a silent shadow
    with pytest.raises(TypeError):
        reg.gauge("sweep_violations")
    # user-supplied label values (campaign ids) are exposition-escaped —
    # one hostile id must not poison the whole scrape
    reg.gauge("farm_campaign_generation").set(1, campaign='a"b\\c\nd')
    assert 'campaign="a\\"b\\\\c\\nd"' in reg.to_prom()


# -------------------------------------------- nemesis occurrence-row schema


def test_chaos_occurrence_rows_stable_schema_roundtrip():
    """The per-occurrence fire rows the telemetry sink serializes
    (docs/nemesis.md "Occurrence rows"): key order clause,k,lanes; row
    order = OCC_CLAUSES registry order then ascending k — stable however
    the summary dict was ordered — and a JSON round trip is identity."""
    from madsim_tpu.nemesis import OCC_CLAUSES

    summary = {  # deliberately scrambled insertion order
        "occfires_spike_k0": 7,
        "occfires_crash_k2": 1,
        "occfires_partition_k1": 2,
        "occfires_crash_k0": 3,
        "fires_crash": 4,  # clause totals are NOT occurrence rows
    }
    rows = telemetry.chaos_rows(summary)
    assert rows == [
        {"clause": "crash", "k": 0, "lanes": 3},
        {"clause": "crash", "k": 2, "lanes": 1},
        {"clause": "partition", "k": 1, "lanes": 2},
        {"clause": "spike", "k": 0, "lanes": 7},
    ]
    # row order follows the OCC_CLAUSES registry, not string sort luck
    clauses = [r["clause"] for r in rows]
    assert clauses == sorted(
        clauses, key=lambda c: OCC_CLAUSES.index(c)
    )
    # key order inside each row is part of the schema (json preserves it)
    for r in rows:
        assert list(r) == ["clause", "k", "lanes"]
    assert json.loads(json.dumps(rows)) == rows
    assert telemetry.chaos_rows({}) == []


def test_chaos_rows_carry_disk_occurrences_end_to_end():
    """The r18 durability clause in the occurrence-row schema: `disk`
    rows sort after the older clauses (OCC_CLAUSES registry order), and a
    real wal run's summary emits exactly one row per fired disk episode —
    the k set equals the lane's occ_fired bitmask."""
    summary = {
        "occfires_disk_k1": 5,
        "occfires_crash_k0": 1,
        "occfires_disk_k0": 9,
    }
    assert telemetry.chaos_rows(summary) == [
        {"clause": "crash", "k": 0, "lanes": 1},
        {"clause": "disk", "k": 0, "lanes": 9},
        {"clause": "disk", "k": 1, "lanes": 5},
    ]

    import jax.numpy as jnp
    import numpy as np

    from madsim_tpu.nemesis import OCC_ROW
    from madsim_tpu.tpu import BatchedSim, summarize
    from madsim_tpu.tpu.wal import wal_workload

    wl = wal_workload(virtual_secs=3.0)
    sim = BatchedSim(wl.spec, wl.config)
    st = sim.run(jnp.asarray([5], jnp.uint32), max_steps=40_000)
    s = summarize(st)
    mask = int(np.asarray(st.occ_fired)[0, OCC_ROW["disk"]])
    ks = {k for k in range(32) if (mask >> k) & 1}
    assert ks, "the wal workload's DiskFault clause must fire by 3s"
    got = {
        r["k"] for r in telemetry.chaos_rows(s) if r["clause"] == "disk"
    }
    assert got == ks
    # the clause's three fire kinds ride the totals vocabulary too
    assert s.get("fires_disk_slow", 0) >= 1
    assert s.get("fires_disk_crash", 0) >= 1


# ------------------------------------------------------------ lint satellite


def test_telemetry_module_passes_entropy_lint_without_pragmas():
    """telemetry.py uses only `time.perf_counter` (allowlisted monotonic
    clock): the ambient-entropy rule passes with ZERO violations and the
    module carries no `# madsim: allow` pragma."""
    from madsim_tpu.analysis.lint import check_entropy_file, repo_root

    root = repo_root()
    path = os.path.join(root, "madsim_tpu", "telemetry.py")
    res = check_entropy_file(path, root)
    assert res.violations == [], res.violations
    assert res.checked > 0  # the rule actually scanned call sites
    with open(path) as f:
        src = f.read()
    assert "madsim: allow" not in src
    assert "perf_counter" in src  # the allowlisted clock is what it uses


# ------------------------------------------------------------------ spans


def test_span_is_noop_singleton_when_disabled():
    a, b = telemetry.span("x"), telemetry.span("y", q=1)
    assert a is b  # no per-call allocation on the disabled path
    with a:
        pass
    telemetry.enable()
    assert telemetry.span("x") is not telemetry.span("x")
    telemetry.disable()
    assert telemetry.spans() == []


def test_spans_capture_threads_and_export_wellformed_perfetto(tmp_path):
    telemetry.enable()

    def worker():
        with telemetry.span("slice", campaign="c1", device=1):
            time.sleep(0.002)

    with telemetry.span("dispatch", off=0):
        time.sleep(0.001)
    t = threading.Thread(target=worker, name="lane-1")
    t.start()
    t.join()
    recs = telemetry.spans()
    assert sorted(r.name for r in recs) == ["dispatch", "slice"]
    assert {r.thread for r in recs} == {"MainThread", "lane-1"}
    assert all(r.dur_s > 0 and r.t0_s >= 0 for r in recs)
    # the registry histogram sees every span, labeled by span and site
    h = telemetry.get_registry().histogram("span_seconds")
    assert h.snapshot(span="dispatch", site="")["count"] == 1
    assert h.snapshot(span="slice", site="")["count"] == 1

    path = str(tmp_path / "loop.perfetto.json")
    telemetry.write_spans_perfetto(path)
    telemetry.disable()
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    assert len(slices) == 2
    for e in evs:
        assert {"ph", "pid", "ts"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] > 0 and "tid" in e and e["name"]
    # one wall-clock track per thread
    threads = [
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert sorted(threads) == ["MainThread", "lane-1"]


def test_host_runtime_metrics_route_through_registry():
    """The host half of the sweep vocabulary: RuntimeMetrics censuses,
    occupancy, dispatch rounds and loop wall route through the same
    registry (and export flat via to_telemetry)."""
    import madsim_tpu as ms

    rt = ms.Runtime(seed=1)

    async def body():
        async def forever():
            while True:
                await ms.time.sleep(1.0)

        node = ms.Handle.current().create_node().name("n").build()
        node.spawn(forever())
        await ms.time.sleep(2.0)

    rt.block_on(body())
    m = rt.handle.metrics()
    flat = m.to_telemetry()
    assert flat["host_nodes"] == 2  # main + n
    assert flat["host_dispatches"] > 0 and flat["host_device_ms"] >= 0
    assert 0 < flat["host_occupancy"] <= 1
    assert json.loads(json.dumps(flat)) == flat

    reg = telemetry.enable()
    telemetry.record_runtime_metrics(m, runtime="rt1")
    telemetry.disable()
    assert reg.gauge("host_nodes").value(runtime="rt1") == 2
    assert reg.counter("host_dispatches").value(runtime="rt1") == \
        flat["host_dispatches"]
    assert reg.gauge("host_occupancy").value(runtime="rt1") == \
        m.occupancy


# ----------------------------------------------- bit-identity (acceptance)


@pytest.mark.chaos
def test_explorer_fingerprint_bit_identical_telemetry_on_off(tmp_path):
    """The hard constraint, verified not promised: the SAME search with
    telemetry fully on (registry + JSONL sink + spans) fingerprints
    bit-identically to the bare run, and the sink actually captured the
    explorer's generation stats while doing so."""
    from madsim_tpu.explore import Explorer

    from tests.test_explore import _planted_workload

    wl = _planted_workload()
    off = Explorer(
        wl, meta_seed=11, lanes=16, chunk=8, shrink_violations=False,
    ).run(2)

    telemetry.enable(out_dir=str(tmp_path))
    on = Explorer(
        wl, meta_seed=11, lanes=16, chunk=8, shrink_violations=False,
    ).run(2)
    reg = telemetry.get_registry()
    assert reg.gauge("explore_generations").value(meta_seed=11) == 2
    assert reg.gauge("explore_coverage_bits").value(meta_seed=11) == \
        on.coverage_bits
    telemetry.disable()

    assert on.fingerprint() == off.fingerprint()
    assert on.coverage_curve == off.coverage_curve
    assert on.corpus_digest == off.corpus_digest
    # and the stream it produced validates line by line
    events = telemetry.read_events(str(tmp_path / "events.jsonl"))
    assert any(e["name"] == "explore_coverage_bits" for e in events)
    assert any(e["kind"] == "span" for e in events)


@pytest.mark.slow
@pytest.mark.chaos
def test_golden_digest_bit_identical_with_telemetry_on():
    """The canonical raft golden trajectory digest (pinned in
    tests/test_state_layout.py) is reproduced exactly with telemetry
    enabled — the engine's device programs are untouched by capture."""
    from tests import test_state_layout as tsl

    telemetry.enable()
    try:
        tsl._golden_one("raft")  # asserts canonical_digest == GOLDEN
    finally:
        telemetry.disable()


# ----------------------------------------- virtual-time Perfetto timelines


@pytest.fixture(scope="module")
def violating_sweep(tmp_path_factory):
    """One planted-bug sweep with telemetry on, shared by the timeline and
    metrics tests: 24 seeds of the deposed-leader re-stamp workload, one
    violating seed traced (and its timeline auto-written)."""
    from madsim_tpu.tpu.batch import run_batch

    tdir = str(tmp_path_factory.mktemp("telem-sweep"))
    wl = _sched_workload()
    telemetry.enable(out_dir=tdir)
    try:
        result = run_batch(
            range(24), wl, repro_on_host=False, max_traces=1,
        )
    finally:
        telemetry.disable()
    assert result.violations > 0, result.summary
    return wl, result, tdir


def _timeline_anchors(doc):
    """Anchor events (the 1:1 TraceEvent images): deliveries are X slices
    with cat=deliver, everything else instants."""
    return [
        e for e in doc["traceEvents"]
        if (e["ph"] == "X" and e.get("cat") == "deliver") or e["ph"] == "i"
    ]


@pytest.mark.chaos
def test_perfetto_timeline_matches_format_trace_event_for_event(
    violating_sweep,
):
    """Acceptance: the Perfetto file of a violating raft replay carries
    the same information as the format_trace text — every TraceEvent has
    exactly one anchor (track slice or instant) at its virtual time, every
    delivery one src→dst flow pair, and the JSON is well-formed
    Chrome-trace (pid/tid/ts/ph on every event)."""
    from madsim_tpu.tpu.trace import format_trace

    wl, result, _ = violating_sweep
    seed, events = next(iter(result.traces.items()))
    assert any(e.kind == "violation" for e in events)
    text_lines = format_trace(events).splitlines()
    assert len(text_lines) == len(events)

    doc = telemetry.perfetto_from_events(
        events, n_nodes=wl.spec.n_nodes, label=f"raft seed {seed}"
    )
    doc = json.loads(json.dumps(doc))  # what a file reader would see
    assert doc["otherData"]["format"] == telemetry.TELEMETRY_FORMAT

    # -- well-formed chrome trace: required fields on every event --------
    for e in doc["traceEvents"]:
        assert {"ph", "pid", "ts"} <= set(e), e
        if e["ph"] != "M":
            assert "tid" in e, e
        if e["ph"] == "X":
            assert e["dur"] >= 1
        if e["ph"] == "i":
            assert e["s"] in ("t", "p", "g")

    # -- event-for-event: one anchor per TraceEvent ----------------------
    anchors = _timeline_anchors(doc)
    assert len(anchors) == len(events)
    pool = list(anchors)

    def take(pred, te):
        for i, e in enumerate(pool):
            if pred(e):
                return pool.pop(i)
        raise AssertionError(f"no timeline anchor for {te}")

    for te in events:
        if te.kind == "deliver":
            name = te.msg_name or f"kind{te.msg_kind}"
            a = take(
                lambda e: e["ph"] == "X" and e.get("cat") == "deliver"
                and e["ts"] == te.t_us and e["tid"] == te.node
                and e["name"] == name
                and e["args"]["src"] == te.src
                and e["args"]["payload"] == list(te.payload or ()),
                te,
            )
            assert a["args"]["step"] == te.step
        elif te.kind == "timer":
            take(
                lambda e: e["ph"] == "i" and e.get("cat") == "timer"
                and e["ts"] == te.t_us and e["tid"] == te.node, te,
            )
        elif te.kind in ("violation", "deadlock"):
            take(
                lambda e: e["ph"] == "i" and e.get("cat") == "invariant"
                and e["name"] == te.kind and e["ts"] == te.t_us, te,
            )
        else:
            take(
                lambda e: e["ph"] == "i" and e.get("cat") == "chaos"
                and e["ts"] == te.t_us
                and e["name"].split(" ")[0] == te.kind, te,
            )
    assert pool == []  # nothing fabricated either

    # -- deliveries flow src→dst: one s/f pair per delivery, ids 1:1 -----
    delivers = [e for e in events if e.kind == "deliver"]
    starts = [e for e in doc["traceEvents"] if e["ph"] == "s"]
    ends = [e for e in doc["traceEvents"] if e["ph"] == "f"]
    assert len(starts) == len(ends) == len(delivers)
    by_id = {e["id"]: e for e in starts}
    assert len(by_id) == len(starts)  # unique flow ids
    src_dst = sorted((e.src, e.node, e.t_us) for e in delivers)
    flow_pairs = sorted(
        (by_id[f["id"]]["tid"], f["tid"], f["ts"]) for f in ends
    )
    assert flow_pairs == src_dst

    # -- the violation is visible as a process-scoped marker -------------
    v = [
        e for e in doc["traceEvents"]
        if e.get("cat") == "invariant" and e["name"] == "violation"
    ]
    assert len(v) == 1 and v[0]["s"] == "p"

    # node tracks are declared for every node
    names = {
        e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"
    }
    assert {f"node{n}" for n in range(wl.spec.n_nodes)} <= names


def test_perfetto_flow_pairing_uses_lineage_edges():
    """The r12 flow-pairing fix: with TWO same-kind messages in flight on
    ONE link, only the lineage `sent_eid` edges can draw the right
    arrows — any (src, dst, kind) matching (and the old fall-back of
    anchoring at the delivery instant) ties them. The regression: two
    deliveries node0->node1 of the same kind, sent at t=100 and t=200,
    delivered OUT OF ORDER (reorder window) at t=1300 and t=1250 — the
    arrow of the t=1300 delivery must start at t=100, the t=1250 one at
    t=200."""
    from madsim_tpu.tpu.trace import TraceEvent

    events = [
        TraceEvent(step=1, t_us=100, kind="timer", node=0, eid=1, lam=1),
        TraceEvent(step=2, t_us=200, kind="timer", node=0, eid=2, lam=2),
        # second send overtakes the first (same src, dst, kind!)
        TraceEvent(step=5, t_us=1250, kind="deliver", node=1, src=0,
                   msg_kind=3, msg_name="PING", eid=3, sent_eid=2, lam=4),
        TraceEvent(step=6, t_us=1300, kind="deliver", node=1, src=0,
                   msg_kind=3, msg_name="PING", eid=4, sent_eid=1, lam=6),
    ]
    doc = telemetry.perfetto_from_events(events, n_nodes=2)
    starts = {e["id"]: e for e in doc["traceEvents"] if e["ph"] == "s"}
    ends = {e["id"]: e for e in doc["traceEvents"] if e["ph"] == "f"}
    assert len(starts) == len(ends) == 2
    arrow_of = {ends[i]["ts"]: starts[i]["ts"] for i in ends}
    assert arrow_of == {1250: 200, 1300: 100}, (
        "flow arrows must follow the sent_eid edges, not delivery order"
    )
    # delivery anchors expose the edge for tooltip-level debugging
    xs = [e for e in doc["traceEvents"]
          if e["ph"] == "X" and e.get("cat") == "deliver"]
    assert sorted((x["args"]["eid"], x["args"]["sent_eid"]) for x in xs) \
        == [(3, 2), (4, 1)]
    # legacy traces (no lineage) keep the old fallback: arrows anchored
    # at the delivery instant, never a wrong-origin guess
    legacy = [dataclasses.replace(e, eid=-1, sent_eid=-1) for e in events]
    doc2 = telemetry.perfetto_from_events(legacy, n_nodes=2)
    for s in (e for e in doc2["traceEvents"] if e["ph"] == "s"):
        assert s["ts"] in (1250, 1300)


def test_perfetto_lineage_flow_on_real_trace():
    """End to end on a real lineage-enabled traced replay: every flow
    arrow starts at its send event's time on the source track, strictly
    before (or at) the delivery it feeds."""
    from madsim_tpu.tpu import make_raft_spec
    from madsim_tpu.tpu.engine import BatchedSim
    from madsim_tpu.tpu.trace import extract_trace

    spec = make_raft_spec()
    sim = BatchedSim(spec, None, lineage=True)
    _, recs = sim.run_traced(3, max_steps=250)
    events = extract_trace(recs, kind_names=spec.msg_kind_names)
    by_eid = {e.eid: e for e in events if e.eid >= 0}
    doc = telemetry.perfetto_from_events(events, n_nodes=spec.n_nodes)
    starts = {e["id"]: e for e in doc["traceEvents"] if e["ph"] == "s"}
    ends = {e["id"]: e for e in doc["traceEvents"] if e["ph"] == "f"}
    delivers = [e for e in events if e.kind == "deliver"]
    assert delivers and len(starts) == len(delivers)
    checked = 0
    for i, f in ends.items():
        s = starts[i]
        assert s["ts"] <= f["ts"]
        # the arrow's start is a real send event's (track, time)
        d = next(
            e for e in delivers
            if e.t_us == f["ts"] and e.node == f["tid"]
        )
        send = by_eid[d.sent_eid]
        assert (s["tid"], s["ts"]) == (send.node, send.t_us)
        checked += 1
    assert checked == len(delivers)


@pytest.mark.chaos
def test_run_batch_routes_metrics_and_writes_timeline(violating_sweep):
    """With telemetry enabled, run_batch emits the sweep's summary through
    the registry (violations, occupancy, dispatches, wall_ms, chaos
    fires per clause AND per occurrence) and drops the traced violation's
    timeline next to the events stream — all post-sweep, observe-only."""
    wl, result, tdir = violating_sweep
    seed = next(iter(result.traces))

    # the auto-written timeline parses and anchors 1:1 with the trace
    tpath = os.path.join(tdir, f"{wl.spec.name}-seed{seed}.perfetto.json")
    assert os.path.exists(tpath)
    with open(tpath) as f:
        doc = json.load(f)
    assert len(_timeline_anchors(doc)) == len(result.traces[seed])

    # the events stream validates and carries the routed summary
    events = telemetry.read_events(os.path.join(tdir, "events.jsonl"))
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert sum(
        e["value"] for e in by_name["sweep_violations"]
    ) == result.violations
    assert sum(
        e["value"] for e in by_name["sweep_dispatches"]
    ) == result.dispatches
    assert "sweep_wall_ms" in by_name and "sweep_occupancy" in by_name
    # chaos fires per clause and per occurrence rode through
    fire_clauses = {
        e["labels"]["clause"] for e in by_name.get("chaos_fires", [])
    }
    assert {"crash", "partition"} <= fire_clauses
    occ_rows = by_name.get("chaos_occurrence_lanes", [])
    assert occ_rows and all("k" in e["labels"] for e in occ_rows)
    # spans of the pipelined loop are on the stream too
    sites = {
        e["labels"].get("site") for e in events if e["kind"] == "span"
    }
    assert {"run_batch"} <= sites


# ------------------------------------------------------- repro --perfetto


@pytest.mark.slow
@pytest.mark.chaos
def test_repro_trace_perfetto_writes_timeline_next_to_bundle(
    violating_sweep, tmp_path, capsys,
):
    """Satellite: `python -m madsim_tpu.repro bundle.json --trace 5
    --perfetto` replays the bundle, prints the trace tail, and writes the
    timeline next to the bundle — bundle schema unchanged."""
    from madsim_tpu import repro, triage

    wl, result, _ = violating_sweep
    seed = result.violating_seeds[0]
    sr = triage.shrink_seed(
        wl, seed, out_dir=str(tmp_path),
        spec_ref="tests.test_triage:planted_restamp_spec",
    )
    bundle_doc = json.load(open(sr.bundle_path))

    rc = repro.main([sr.bundle_path, "--trace", "5", "--perfetto"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "device replay OK" in out
    root, _ = os.path.splitext(sr.bundle_path)
    tpath = f"{root}.perfetto.json"
    assert f"perfetto timeline: {tpath}" in out
    with open(tpath) as f:
        doc = json.load(f)
    anchors = _timeline_anchors(doc)
    assert anchors and any(
        e.get("cat") == "invariant" and e["name"] == "violation"
        for e in anchors
    )
    # flag is additive: the bundle on disk is byte-for-byte what the
    # shrinker wrote (schema unchanged)
    assert json.load(open(sr.bundle_path)) == bundle_doc


# ----------------------------------------------------- farm status surface


def _stub_serve(d, requests, **kw):
    """campaign.serve with filesystem-only stub campaigns."""
    from madsim_tpu import campaign
    from tests.test_campaign import _report

    class Stub:
        def __init__(self, cid):
            self.cid, self.generation, self.bugs = cid, 0, []

        def run(self, g):
            self.generation += g
            time.sleep(0.001)  # widen the read/replace race window
            return _report()

        def checkpoint(self):
            os.makedirs(
                os.path.join(d, "campaigns", self.cid), exist_ok=True
            )

    os.makedirs(os.path.join(d, "queue"), exist_ok=True)
    for name, req in requests.items():
        with open(os.path.join(d, "queue", f"{name}.json"), "w") as f:
            json.dump(req, f)
    return campaign.serve(
        d, out=lambda s: None,
        factory=lambda r, cd, rd, log: Stub(r["id"]),
        sleep=lambda s: None, **kw,
    )


def test_serve_status_surface_contents(tmp_path):
    from madsim_tpu import campaign

    d = str(tmp_path / "svc")
    res = _stub_serve(
        d,
        {"a": {"workload": "raft", "generations": 3},
         "b": {"workload": "raft", "generations": 1}},
        max_rounds=10, idle_rounds=1, devices=["devA", "devB"],
    )
    assert res["completed"] == ["b", "a"]
    with open(os.path.join(d, campaign.STATUS)) as f:
        status = json.load(f)
    assert status["format"] == telemetry.FARM_STATUS_FORMAT
    assert status["queue_depth"] == 0 and status["active"] == {}
    assert sorted(status["completed"]) == ["a", "b"]
    assert status["devices"] == 2 and len(status["per_device"]) == 2
    for row in status["per_device"]:
        assert row["busy_s"] > 0 and 0 < row["occupancy"] <= 1
        assert row["seeds_run"] > 0 and row["seeds_per_sec"] > 0
    # the textfile face carries the same numbers, prometheus-shaped
    with open(os.path.join(d, campaign.METRICS_TEXTFILE)) as f:
        prom = f.read()
    assert "madsim_farm_queue_depth 0" in prom
    assert "madsim_farm_completed_campaigns 2" in prom
    assert 'madsim_farm_device_occupancy{device="0"}' in prom
    assert 'madsim_farm_device_seeds_per_sec{device="1"}' in prom
    # mid-flight snapshot shows the live cursors: rerun with a round cap
    d2 = str(tmp_path / "svc2")
    _stub_serve(
        d2, {"c": {"workload": "raft", "generations": 5}},
        max_rounds=2, idle_rounds=1,
    )
    with open(os.path.join(d2, campaign.STATUS)) as f:
        live = json.load(f)
    assert live["active"]["c"]["generation"] == 2
    assert live["active"]["c"]["remaining"] == 3
    # `telemetry render` reads the surface (dir or file)
    assert telemetry.main(["render", d2]) == 0


def test_serve_status_updates_are_atomic(tmp_path):
    """Reader-never-sees-a-torn-file: a thread hammering status.json +
    metrics.prom throughout a many-round serve sees only complete,
    parseable documents (tmp+os.replace), and no tmp litter survives."""
    from madsim_tpu import campaign

    d = str(tmp_path / "svc")
    status_path = os.path.join(d, campaign.STATUS)
    prom_path = os.path.join(d, campaign.METRICS_TEXTFILE)
    stop = threading.Event()
    torn, reads = [], [0]

    def reader():
        while not stop.is_set():
            for path in (status_path, prom_path):
                try:
                    with open(path) as f:
                        text = f.read()
                except FileNotFoundError:
                    continue  # not written yet — fine, never torn
                reads[0] += 1
                try:
                    if path is status_path:
                        doc = json.loads(text)
                        if doc.get("format") != telemetry.FARM_STATUS_FORMAT:
                            torn.append(f"missing format: {text[:80]!r}")
                    elif text and not text.endswith("\n"):
                        torn.append(f"truncated textfile: {text[-40:]!r}")
                except json.JSONDecodeError as e:
                    torn.append(f"{e}: {text[:80]!r}")

    t = threading.Thread(target=reader, name="scraper")
    t.start()
    try:
        _stub_serve(
            d, {"a": {"workload": "raft", "generations": 40}},
            max_rounds=40, idle_rounds=1,
        )
    finally:
        stop.set()
        t.join()
    assert torn == [], torn[:5]
    assert reads[0] > 10  # the reader genuinely raced the writer
    assert not [p for p in os.listdir(d) if ".tmp" in p]


# ------------------------------------------------------------------- CLI


def test_cli_tail_and_render(tmp_path, capsys):
    reg = telemetry.enable(out_dir=str(tmp_path))
    reg.counter("sweep_violations").inc(2, workload="raft")
    with telemetry.span("dispatch"):
        pass
    telemetry.disable()
    events_path = str(tmp_path / "events.jsonl")

    assert telemetry.main(["tail", events_path, "-n", "10"]) == 0
    out = capsys.readouterr().out
    assert "sweep_violations{workload=raft} = 2" in out
    assert "span dispatch" in out

    # --validate catches corrupt lines
    with open(events_path, "a") as f:
        f.write('{"format": "nope"}\n')
    assert telemetry.main(
        ["tail", events_path, "--validate"]
    ) == 1
    capsys.readouterr()

    # render recognizes a timeline document too
    tl = str(tmp_path / "t.json")
    telemetry.write_perfetto(tl, [])
    assert telemetry.main(["render", tl]) == 0
    assert "chrome-trace" in capsys.readouterr().out
    assert telemetry.main(["render", str(tmp_path / "missing.json")]) == 1


# ------------------------------------------------------- overhead budget


@pytest.mark.slow
@pytest.mark.chaos
def test_telemetry_overhead_under_2pct():
    """The bench's telemetry_overhead key on the smoke workload: the
    span-wrapped dispatch loop costs <2% over bare (min-of-repeats damps
    scheduler noise; the per-span µs cost is reported alongside). The
    true span cost is ~10µs x 8 spans on a ~0.4s loop (0.02%); one
    re-measure absorbs the rare CI scheduler spike that dwarfs it."""
    import bench

    r = bench.bench_telemetry_overhead(
        lanes=128, virtual_secs=0.3, iters=4, repeats=6
    )
    if r["overhead_pct"] >= 2.0:  # pragma: no cover - noise retry
        r = bench.bench_telemetry_overhead(
            lanes=128, virtual_secs=0.3, iters=4, repeats=6
        )
    assert r["overhead_pct"] < 2.0, r
    # sanity on the budget arithmetic: µs-scale spans on ms-scale
    # dispatches — the analytic bound agrees with the measured one
    analytic_pct = (
        r["spans_per_dispatch"] * r["span_us"] * r["dispatches"]
        / (r["bare_s"] * 1e6) * 100
    )
    assert analytic_pct < 2.0, r
