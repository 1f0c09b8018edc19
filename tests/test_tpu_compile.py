"""The FL path's three Pallas kernels compile for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unsupported primitives, scalar stores to VMEM, unaligned slices, fast
memory overruns.  These tests hand the TPU compiler the kernels at the
sizes ``chip_smoke.py`` runs, for a chip that is described and not
attached, and check that the executable holds the Mosaic custom call.
They run no kernel and time nothing.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fleet_state.kernel import segment_index_pallas
from repro.kernels.pairwise_rank.kernel import pairwise_rank_pallas
from repro.kernels.select_topk.kernel import select_topk_pallas

V5E_HBM_BYTES = 16 * 2**30      # one v5e chip (Google Cloud "TPU v5e" docs)
QNET_IN, QNET_HIDDEN = 6, 64    # paper6 feature set, repro.core.qnet defaults
FLEET = 100_000                 # chip_smoke.py's fleet
PROBE_K = 52                    # FedRank's probe cut at K=32
SELECT_N = 64                   # FedRank.select's full probe-cohort order
TRACE_SEGMENTS = 2369           # trace-synthetic-week's compiled timeline
IL_BATCH, IL_COHORT = 16, 30    # pretrain_qnet batch of expert cohorts


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile written to the persistent cache cannot be
    # read back without the chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES
    return mem


@pytest.mark.parametrize("n,k", [(FLEET, PROBE_K), (SELECT_N, SELECT_N)])
def test_select_topk_compiles(one_chip, n, k):
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    q = {"w1": spec((QNET_IN, QNET_HIDDEN)), "b1": spec((QNET_HIDDEN,)),
         "w2": spec((QNET_HIDDEN, QNET_HIDDEN)), "b2": spec((QNET_HIDDEN,)),
         "w3": spec((QNET_HIDDEN, 1)), "b3": spec((1,))}
    mem = _compile(functools.partial(select_topk_pallas, k=k, interpret=False),
                   q, spec((n, QNET_IN)), spec((n,)), spec((n,)))
    # the carried top-K is the only output: (values, indices) of K_pad
    assert mem.output_size_in_bytes < 4096


@pytest.mark.parametrize("s", [300, TRACE_SEGMENTS])
def test_fleet_state_compiles(one_chip, s):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(functools.partial(segment_index_pallas, interpret=False),
             spec((s,), jnp.int32), spec((s,), jnp.int32),
             spec((s,), jnp.float32), spec((FLEET,), jnp.int32),
             spec((FLEET,), jnp.int32), spec((FLEET,), jnp.float32))


@pytest.mark.parametrize("shape", [(IL_BATCH, IL_COHORT), (1, 4096)],
                         ids=["il_batch", "pool_4096"])
def test_pairwise_rank_compiles(one_chip, shape):
    """Under vmap, as ``pretrain_qnet`` calls it per expert cohort."""
    spec = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fn = jax.vmap(functools.partial(pairwise_rank_pallas, hard=True,
                                    interpret=False))
    _compile(fn, spec, spec, spec)
