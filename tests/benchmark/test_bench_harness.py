"""The benchmark harness on the CPU: discovery by name, the window's
arithmetic, the counts of attempted and failed work, the copied
workload factories, seeds, and the exit without a chip."""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.lib import harness, seeds

ROOT = harness.ROOT


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_cell_and_metrics_are_found_by_name(cell):
    c = harness.load_cell(cell)
    assert c.traffic["kind"] in harness.KINDS
    assert callable(c.factory.build) and callable(c.factory.reference)
    assert "setup_s" in {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(c.metric_reader(m["name"]).read)


def test_a_new_cell_is_found_from_its_files_alone(tmp_path):
    b = bench()
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    (tmp_path / "benchmark" / "traffic" / "tiny.json").write_text(
        json.dumps({"kind": "sweep", "seeds_per_call": 8,
                    "sample_lanes_per_chunk": 2}))
    b["workloads"].append({"name": "raft5.tiny", "config": "raft5",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    b["end_to_end"][0]["workloads"].append("raft5.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = harness.load_cell("raft5.tiny", bench_dir=str(tmp_path / "benchmark"))
    assert c.traffic["seeds_per_call"] == 8
    assert {m["name"] for m in c.end_to_end} == {"seeds_per_s", "setup_s"}
    # a metric without a workloads list is reported by every cell that
    # reports the end-to-end metric it moves
    assert not [m for m in c.per_layer if "workloads" in m]


def test_window_ends_at_a_call_boundary_and_counts_all_its_work():
    now = [0.0]
    durations = [3.0, 4.0, 5.0, 6.0]

    def clock():
        return now[0]

    def call(k):
        now[0] += durations[k]
        return {"seeds": 100}

    elapsed, recs = harness.window(10.0, call, clock)
    # calls begin at 0, 3 and 7 (inside 10 s); the third ends at 12
    assert len(recs) == 3 and elapsed == 12.0
    run = harness.Run(cell=None, elapsed=elapsed, records=recs)
    assert run.total("seeds") / run.elapsed == 300 / 12.0


def _fake_result(steps, violated, overflow_last, total_overflow, max_steps):
    state = types.SimpleNamespace(overflow=np.asarray(overflow_last))
    return types.SimpleNamespace(
        retired_step=np.asarray(steps), violated=np.asarray(violated),
        violations=int(np.sum(violated)), state=state,
        summary={"total_overflow": total_overflow, "total_events": 5,
                 "occupancy": 0.305})


def test_sweep_counts_failed_seeds():
    steps = [10, 0, 100, 12]  # no step, and max_steps reached
    r = _fake_result(steps, [False, False, False, True], [0, 0, 0, 2], 2, 100)
    rec = harness.sweep_record(r, np.arange(4, dtype=np.uint32), 100, [4])
    assert rec["unfinished"] == 2 and rec["failed"] == 3
    assert rec["violations"] == 1 and rec["rows"] == 4
    assert rec["occupancy"] == 0.305


def test_loop_steps_are_each_chunk_s_longest_lane():
    r = _fake_result([10, 30, 5, 7, 9], [False] * 5, [0] * 2, 0, 100)
    rec = harness.sweep_record(r, np.arange(5, dtype=np.uint32), 100, [3, 2])
    assert rec["loop_steps"] == 30 + 9


def test_engine_step_and_occupancy_read_the_program_s_counts():
    from benchmark.lib import trace

    cell = harness.load_cell("raft5.sweep")
    ev = {"devices": {"/device:TPU:0": {
        "ops": [], "modules": [["jit__run(1)", 0, 300_000],
                               ["jit__run(1)", 400_000, 100_000],
                               ["jit__run_traced(2)", 600_000, 50_000],
                               ["jit__init(3)", 700_000, 10]]}}, "host": []}
    run = harness.Run(cell=cell, records=[
        {"loop_steps": 40, "occupancy": 0.8},
        {"loop_steps": 99, "occupancy": 0.9}])
    run.trace = trace.reduce(ev, 0, 1_000_000)
    # the first call is traced: 400 us of `_run` over 40 iterations
    assert cell.metric_reader("engine_step_us").read(run) == 10.0
    assert cell.metric_reader("occupancy_pct").read(run) == \
        pytest.approx(85.0)


def _run(kind, records, checks=()):
    cell = types.SimpleNamespace(traffic={"kind": kind}, per_layer=[],
                                 end_to_end=[], name="x")
    return harness.Run(cell=cell, elapsed=1.0, records=records,
                       checks=list(checks), notes={"setup_s": 1.0})


def test_attempted_and_failed_per_kind():
    dev = [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    sweep = _run("sweep", [{"seeds": 10, "failed": 1}, {"seeds": 10,
                                                         "failed": 0}])
    res = harness.result(sweep, dev, trace=False)
    assert (res["attempted"], res["failed"]) == (20, 1)
    triage = _run("triage", [{"cycles": 1, "failed": 0, "bundle": "a"},
                             {"cycles": 1, "failed": 1, "bundle": None}],
                  [("cycles_without_bundle", 1, 0, "at_most")])
    res = harness.result(triage, dev, trace=False)
    assert (res["attempted"], res["failed"]) == (2, 1)
    assert res["correct"] is False
    assert list(res)[-1] == "checks"


def test_copied_factories_start_identical_to_the_program_s():
    sys.path.insert(0, ROOT)
    from bench import raft_bench_config
    from benches.ttfb import restamp_workload

    sweep = harness.load_cell("raft5.sweep")
    wl = sweep.factory.build(sweep.config, sweep.traffic)
    assert wl.config.hash() == raft_bench_config(10.0).hash()
    assert wl.spec.name == "raft5"
    tri = harness.load_cell("raft5.triage")
    wl = tri.factory.build(tri.config, tri.traffic)
    ref = restamp_workload()
    assert wl.config.hash() == ref.config.hash()
    assert wl.spec.name == ref.spec.name
    assert wl.host_repro is None and ref.host_repro is None


def test_seed_blocks_are_fixed_disjoint_and_take_any_whole_number():
    for s in (0, 7, 2**31 + 5, 2**40 + 3):
        a, b = seeds.block(s, 0, 1000), seeds.block(s, 1, 1000)
        assert a.dtype == np.uint32 and a.size == 1000
        assert int(a.max()) < 2**31
        assert np.array_equal(a, seeds.block(s, 0, 1000))
        assert not set(a.tolist()) & set(b.tolist())
        assert not set(seeds.block(s, -1, 1000).tolist()) & set(a.tolist())
    assert not np.array_equal(seeds.block(1, 0, 8), seeds.block(2, 0, 8))


def test_reference_key_schedule_matches_the_engine():
    import jax.numpy as jnp

    from madsim_tpu.tpu import prng

    s = seeds.block(2**31 + 9, 0, 64)
    assert np.array_equal(seeds.key_from_seed(s),
                          np.asarray(prng.key_from(jnp.asarray(s))))



def _restamp_bundle_state():
    with open(os.path.join(ROOT, "tests", "benchmark", "states",
                           "raft5_restamp_bundle.json")) as f:
        d = json.load(f)
    return d, {k: np.asarray(v, np.int64) for k, v in d["state"].items()}


@pytest.mark.parametrize("case", ["as_written", "chain_refolded"])
def test_reference_sees_a_rewritten_entry(case):
    """The driver's refused bundle (PR 22): the re-stamp left every log
    agreeing in its terms, so only the node's chain, folded as each entry
    was written, shows the rewrite. Refolded from the log as it stands,
    the same state is sound."""
    raft5 = harness.load_cell("raft5.triage").factory
    d, s = _restamp_bundle_state()
    if case == "chain_refolded":
        for n in range(s["log_chain"].shape[1]):
            for i, (_t, h, _w) in raft5._entries(s, 0, n).items():
                if i >= s["base"][0, n]:
                    slot = (i - s["base"][0, n] + s["head"][0, n]) \
                        % s["log_chain"].shape[-1]
                    s["log_chain"][0, n, slot] = h
    broken = raft5.reference(s, np.asarray([d["seed"]], np.uint32))[0]
    assert broken == (["entry_rewritten"] if case == "as_written" else [])

def _run_py(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", "raft5.sweep", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_with_no_result_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_s_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    p = _run_py(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
