"""Main-path programs compile for one described TPU v5e at real widths.

Nothing runs: each test lowers a program with ShapeDtypeStructs placed on
one chip of a described v5e topology and compiles it with the TPU
compiler that ships with jaxlib, so a program the chip would refuse (too
much device memory, an unsupported op) fails here at no chip time. The
shape is chip_smoke.py's headline: 1M partitions, and the wire's field
widths for 10M privacy units and star ratings.

The chunk bounding step itself compiles for minutes and stays out of
this file; CHANGES.md (PR 21) records its compile time and
memory_analysis from the rehearsal.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import pipelinedp_tpu as pdp
from pipelinedp_tpu import combiners as combiners_lib
from pipelinedp_tpu import noise_core
from pipelinedp_tpu import partition_selection as ps_lib
from pipelinedp_tpu.aggregate_params import MechanismType
from pipelinedp_tpu.ops import columnar, finalize, wirecodec
from pipelinedp_tpu.ops import noise as noise_ops
from pipelinedp_tpu.ops import selection as selection_ops

N_PARTITIONS = 1_000_000
# The decode's compile time grows with the chunk's rows (the bit-plane
# unpack relayout and the row-length cumsums; CHANGES.md, PR 21): a
# 12.5M-row headline chunk takes minutes alone, so the decode compiles
# at the headline's field widths over a 64k-row chunk.
DECODE_ROWS = 65_536
# A compile of any program here past this many bytes of device memory
# would not fit next to the resident wire on a 16 GB chip.
DEVICE_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e, with the persistent compile cache off
    (a TPU executable written here could not be read back without a
    chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _on(sharding, tree):
    """ShapeDtypeStructs of every array leaf of ``tree``, on ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()
    memory = compiled.memory_analysis()
    used = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes)
    assert 0 < used < DEVICE_BYTES, used


def _key(sharding):
    return jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)


def _column(sharding):
    return jax.ShapeDtypeStruct((N_PARTITIONS,), jnp.float32,
                                sharding=sharding)


def test_add_noise_over_1m_partitions(one_chip):
    scale = 8 * 4 / (1 / 3)
    _compile(
        lambda key, values: noise_ops.add_noise(
            key, values, False, scale, noise_core.laplace_granularity(scale)),
        _key(one_chip), _column(one_chip))


def test_select_partitions_over_1m_partitions(one_chip):
    sp = selection_ops.selection_params_from_strategy(
        ps_lib.TruncatedGeometricPartitionSelection(1 / 3, 1e-6, 8))
    _compile(
        lambda key, counts: selection_ops.select_partitions(
            key, counts, sp, counts > 0),
        _key(one_chip), _column(one_chip))


@pytest.mark.parametrize("value_as_index", [False, True])
def test_wire_decode_at_headline_widths(one_chip, value_as_index):
    # The headline wire: RLE pids (3-byte ids, 10M units), 20 pk planes
    # (1M partitions), 3 value planes (star ratings 1..5).
    fmt = wirecodec.WireFormat(
        bytes_pid=3, bits_pk=20, cap=DECODE_ROWS, ucap=DECODE_ROWS // 10,
        value=wirecodec.ValuePlan(wirecodec.VALUE_PLANES, bits=3, lo=1.0,
                                  scale=1.0))
    row = jax.ShapeDtypeStruct((fmt.width,), jnp.uint8, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(wirecodec.decode_bucket, row, scalar, scalar, fmt=fmt,
             value_as_index=value_as_index)


@pytest.mark.parametrize("public", [False, True])
def test_fused_epilogue_over_1m_partitions(one_chip, public):
    accountant = pdp.NaiveBudgetAccountant(1.0, 1e-6)
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        max_partitions_contributed=8, max_contributions_per_partition=4,
        min_value=0.0, max_value=5.0)
    compound = combiners_lib.create_compound_combiner(params, accountant)
    selection_spec = (None if public else accountant.request_budget(
        mechanism_type=MechanismType.GENERIC))
    accountant.compute_budgets()
    plan, scalars = finalize.build_plan(compound.combiners, params,
                                        selection_spec, is_public=public,
                                        num_partitions=N_PARTITIONS)
    accs = columnar.PartitionAccumulators(
        *(np.zeros(N_PARTITIONS, np.float32) for _ in range(5)))
    key = np.zeros(2, np.uint32)
    operands = finalize.device_operands(plan, scalars, accs, None, key, key)
    _compile(functools.partial(finalize._jit_entry, plan),
             _on(one_chip, operands))
