"""AOT compiles of the main path's sweep programs for a described TPU v5e.

No chip is attached here: the TPU compiler builds each program for a
`v5e:2x2` topology that is only described, which refuses what the chip's
compiler would refuse (shapes, memory, partitioning) at no chip time. The
programs are the ones `chip_smoke.py` runs on the chip: the headline raft
sweep segment (`BatchedSim._run`) at 32,768 lanes on one chip, the kv
linearizability segment at 16,384 lanes, and the raft segment
lane-sharded over the four described chips at 4 x 32,768 lanes (what
`run_batch(mesh="auto")` dispatches on a four-chip host). The raft segment
at run_batch's one-chip default width must also keep its temporaries
under 1.5x its arguments: past that width the step spills.

Only one process at a time may load the TPU library, so the topology is
described inside a fixture of this one file — never at import time — and
only the xdist worker that is handed this file loads it.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import raft_bench_config
from madsim_tpu.tpu import BatchedSim, make_raft_spec
from madsim_tpu.tpu.batch import default_chunk
from madsim_tpu.tpu.kv import kv_workload

CHIP_HBM_BYTES = 16 * 2**30  # one v5e chip
SEGMENT_STEPS = 10_000  # engine.DEFAULT_DISPATCH_STEPS: run_batch's segment


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _state_shapes(sim, lanes, sharding_for):
    st = jax.eval_shape(sim.init, jax.ShapeDtypeStruct((lanes,), jnp.uint32))
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding_for(x)
        ),
        st,
    )


def _compile_run(sim, shapes):
    compiled = BatchedSim._run.lower(sim, shapes, SEGMENT_STEPS).compile()
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
        + m.generated_code_size_in_bytes
    )
    assert 0 < total < CHIP_HBM_BYTES, total
    return compiled


def _headline_sim():
    return BatchedSim(
        make_raft_spec(5, client_rate=0.1, log_capacity=16),
        raft_bench_config(10.0),
    )


@pytest.fixture(scope="module")
def compile_one_chip(topo):
    """Compile a sweep segment for one described chip, once per
    (workload, lanes) in this module."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = {}

    def compile_(workload, lanes):
        if (workload, lanes) not in compiled:
            if workload == "raft":
                sim = _headline_sim()
            else:
                wl = kv_workload(virtual_secs=10.0)
                sim = BatchedSim(wl.spec, wl.config)
            compiled[workload, lanes] = _compile_run(
                sim, _state_shapes(sim, lanes, lambda x: one_chip))
        return compiled[workload, lanes]

    return compile_


@pytest.mark.parametrize("workload,lanes", [("raft", 32_768), ("kv", 16_384)])
def test_sweep_segment_compiles_for_one_v5e(compile_one_chip, workload,
                                            lanes):
    compile_one_chip(workload, lanes)


def test_default_width_keeps_the_raft_step_from_spilling(compile_one_chip):
    # temp / argument bytes of the headline segment on a v5e: 0.14 at
    # 16,384 lanes, 1.07 at 32,768, 1.97 at 65,536, where the step spills
    # through extra async copies and a lane-iteration costs 1.39x more
    m = compile_one_chip("raft", default_chunk(1)).memory_analysis()
    assert m.temp_size_in_bytes < 1.5 * m.argument_size_in_bytes, (
        default_chunk(1), m.temp_size_in_bytes, m.argument_size_in_bytes)


def test_lane_sharded_segment_compiles_for_four_v5e(topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sim = _headline_sim()
    # engine.shard_state's lane sharding: every lane-leading leaf split
    # over the mesh, scalars replicated
    mesh = Mesh(np.array(topo.devices), ("seeds",))
    lane = NamedSharding(mesh, P("seeds"))
    rep = NamedSharding(mesh, P())
    compiled = _compile_run(
        sim,
        _state_shapes(sim, 4 * 32_768, lambda x: rep if x.ndim == 0 else lane),
    )
    # the only cross-chip traffic is the while-loop's any(~done) condition
    assert "all-reduce" in compiled.as_text()
