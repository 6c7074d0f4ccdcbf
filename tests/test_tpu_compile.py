"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached, so a kernel that Mosaic would
refuse (a block shape off the (8, 128) tiling, too much VMEM) fails here
instead of on the chip.  Nothing runs; results are checked on the chip
by ``chip_smoke.py``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bucketcount, mergejoin, semijoin

N_PROBE = 1 << 20          # probe keys: a VP column at ~10^7 triples
N_BUILD = 1 << 18          # build keys: a sorted-unique column
N_BUCKETS = 256

#: a compile may keep this much scratch; the lane-padded (n, 1) layout
#: this guards against needs ~128 bytes per key
MAX_TEMP_BYTES = 64 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= MAX_TEMP_BYTES, mem


@pytest.mark.parametrize("kernel", ["semijoin", "mergejoin"])
def test_band_kernels_compile_for_v5e(one_chip, kernel):
    fn = {"semijoin": semijoin.semijoin_membership_pallas,
          "mergejoin": mergejoin.join_probe_pallas}[kernel]
    compiled = jax.jit(lambda a, b: fn(a, b, interpret=False)).lower(
        _i32((N_PROBE,), one_chip), _i32((N_BUILD,), one_chip)).compile()
    _check(compiled)


def test_bucket_count_compiles_for_v5e(one_chip):
    compiled = jax.jit(lambda k: bucketcount.bucket_count_pallas(
        k, N_BUCKETS, interpret=False)).lower(
        _i32((N_PROBE,), one_chip)).compile()
    _check(compiled)


def test_query_program_compiles_for_v5e(one_chip, watdiv_small):
    """A basic template's jitted query program (snowflake F2: scans,
    joins, a bound constant) compiles for the chip from its shapes."""
    from repro.engine import Dataset
    from repro.rdf.workloads import basic_queries

    cat, d, sch = watdiv_small
    query = basic_queries(sch, n_instances=1)["F2"][0]
    executor = Dataset(catalog=cat, dictionary=d).engine("jit") \
        .prepare(query).executor
    args, _ = executor.lower().args_info           # host shapes
    on_chip = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled = executor._jitted.lower(tuple(executor.caps), *on_chip).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= MAX_TEMP_BYTES
