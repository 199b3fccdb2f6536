"""run_batch's default chunk: DEFAULT_CHUNK lanes a device, capped at the
lanes `engine._sum64` sums exactly. An explicit chunk, then a tuned one,
wins; the plan that ran is in the summary and on each dispatch span; and
per-seed rows do not depend on the plan."""

import jax
import numpy as np
import pytest

import madsim_tpu.telemetry as telemetry
from madsim_tpu import workloads as registry
from madsim_tpu.tpu import batch, make_raft_spec
from madsim_tpu.tpu.batch import BatchWorkload, default_chunk, run_batch

# the real rule scaled down 8,192 times: 4 lanes a device, at most 16
SMALL_PER_DEVICE, SMALL_CAP = 4, 16


@pytest.fixture(autouse=True)
def _telemetry_reset():
    telemetry.disable()
    yield
    telemetry.disable()


@pytest.fixture
def small_plan(monkeypatch):
    monkeypatch.setattr(batch, "DEFAULT_CHUNK", SMALL_PER_DEVICE)
    monkeypatch.setattr(batch, "SUM64_MAX_LANES", SMALL_CAP)


def _mesh(n_devices):
    if n_devices == 1:
        return None
    return jax.sharding.Mesh(np.array(jax.devices()[:n_devices]), ("seeds",))


@pytest.mark.parametrize("n_devices,lanes", [
    (1, 32_768), (2, 65_536), (4, 65_536), (8, 65_536),
])
def test_default_chunk_is_lanes_per_device_capped_at_sum64(n_devices, lanes):
    assert default_chunk(n_devices) == lanes


@pytest.mark.parametrize("n_devices,n_seeds,kwargs,chunk,dispatched", [
    # the default: 4 lanes a device, capped at 16
    (1, 10, {}, 4, [4, 4, 2]),
    (2, 10, {}, 8, [8, 2]),
    (4, 10, {}, 10, [12]),  # capped at 16; padded to a device multiple
    (8, 24, {}, 16, [16, 8]),  # capped at 16
    # fewer seeds than a chunk
    (1, 3, {}, 3, [3]),
    # an explicit chunk, then a tuned one, wins
    (1, 10, {"chunk": 5}, 5, [5, 5]),
    (1, 10, {"tuning": {"chunk": 6}}, 6, [6, 4]),
    (1, 10, {"chunk": 5, "tuning": {"chunk": 6}}, 5, [5, 5]),
], ids=["1dev", "2dev", "4dev-capped", "8dev-capped", "few-seeds",
        "explicit", "tuned", "explicit-over-tuned"])
def test_chunk_plan_in_summary_and_dispatch_spans(
    small_plan, n_devices, n_seeds, kwargs, chunk, dispatched,
):
    wl = BatchWorkload(spec=make_raft_spec(n_nodes=3), max_steps=60)
    telemetry.enable()
    r = run_batch(range(n_seeds), wl, mesh=_mesh(n_devices), max_traces=0,
                  **kwargs)
    assert r.summary["n_devices"] == n_devices
    assert r.summary["chunk"] == chunk
    assert r.summary["chunks"] == len(dispatched)
    lanes = [int(s.labels["lanes"]) for s in telemetry.spans()
             if s.label == "dispatch[run_batch]"]
    assert lanes == dispatched


def test_per_seed_rows_identical_under_old_and_new_chunk_plans(monkeypatch):
    """One device: the old plan dispatched the whole cap at once, the new
    one DEFAULT_CHUNK lanes a dispatch. Same rows, seed for seed."""
    wl = registry.workload_factory("backup")(buggy=True)
    seeds = range(24)
    monkeypatch.setattr(batch, "DEFAULT_CHUNK", 8)
    monkeypatch.setattr(batch, "SUM64_MAX_LANES", 16)
    new = run_batch(seeds, wl, mesh=None, repro_on_host=False, max_traces=0)
    old = run_batch(seeds, wl, mesh=None, chunk=16, repro_on_host=False,
                    max_traces=0)
    assert (new.summary["chunks"], old.summary["chunks"]) == (3, 2)
    assert new.violations > 0  # the rows compared carry violations
    for field in ("violated", "violation_step", "retired_step", "deadlocked"):
        np.testing.assert_array_equal(
            getattr(new, field), getattr(old, field), err_msg=field)
