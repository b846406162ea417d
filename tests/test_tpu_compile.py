"""Compiles for a described TPU v5e chip (no chip attached).

The TPU compiler is installed with JAX and compiles for a chip that is
described and not attached, so these tests catch what the chip's compiler
refuses or what does not fit its 16 GB of HBM, at no chip time.  Nothing
runs: they say nothing about results or speed.

The topology is described only inside the module fixture below, never at
import, so every pytest-xdist worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.perf import canonical_scenarios
from repro.kernels.fabric_kernels import rank_in_queue_kernel
from repro.sim import fabric
from repro.sim.faults import build_fault_data
from repro.sim.workloads import RunConfig, _fabric_cfg, _scenario_ticks

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (an entry written here could not be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _lower_fabric(sc, rc: RunConfig, sharding):
    """Lower the jitted fabric program ``run(sc, rc)`` would execute, with
    its inputs placed on ``sharding`` (the same inputs run_fabric_trace
    builds)."""
    fcfg = _fabric_cfg(sc, rc)
    flows, dep = fabric.expand_messages(sc.messages, fcfg.subflows)
    t = sc.topo
    args = (*fabric._flow_arrays(flows, fcfg), jnp.int32(0),
            fabric._arrival_array(sc.messages),
            build_fault_data(fcfg.faults, t.n_tor, t.n_spine,
                             t.hosts_per_tor))
    prog = fabric._get_program(t, len(flows), _scenario_ticks(sc, rc), fcfg,
                               dep)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    return jax.jit(prog.program).lower(*shapes)


@pytest.mark.parametrize("protocol", ["strack", "rocev2"])
def test_warp_program_compiles_for_v5e(v5e_chip, protocol):
    """perm1024's time-warped jnp program (STrack, and RoCEv2 with PFC)
    compiles for one v5e chip and fits its HBM."""
    sc, _ = canonical_scenarios()["perm1024"]
    rc = RunConfig(backend="fabric", protocol=protocol)
    assert _fabric_cfg(sc, rc).pfc_enabled == (protocol == "rocev2")
    mem = _lower_fabric(sc, rc, v5e_chip).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem


def test_ranker_kernel_refuses_tpu_lowering(v5e_chip):
    """The compiled Pallas ranker does not lower for the TPU: its block
    sweep slices with dynamic_index_in_dim / dynamic_update_slice.  A
    change that makes it lower must update this test on purpose."""
    m, n_queues = 1024, 64
    qid = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=v5e_chip)
    flag = jax.ShapeDtypeStruct((m,), jnp.bool_, sharding=v5e_chip)
    fn = jax.jit(lambda q, f: rank_in_queue_kernel(q, f, n_queues,
                                                   interpret=False))
    with pytest.raises(NotImplementedError, match="dynamic_slice"):
        fn.lower(qid, flag).compile()


def test_fused_stage_kernels_refuse_tpu_lowering(v5e_chip):
    """kernel_backend="pallas" fails loudly on the TPU (the fused stage
    cores scatter with .at[].set/add) instead of falling back."""
    sc, _ = canonical_scenarios()["ring8"]
    rc = RunConfig(backend="fabric", kernel_backend="pallas")
    with pytest.raises(NotImplementedError, match="scatter"):
        _lower_fabric(sc, rc, v5e_chip).compile()
