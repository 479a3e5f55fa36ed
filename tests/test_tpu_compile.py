"""Compile rehearsals for the v5e chip, run without the chip.

The TPU compiler is installed here and compiles for a described
`v5e:2x2` topology that is not attached: the served path's kernels at
the widths the server dispatches, each held to the 16 GB of one v5e
chip's HBM by `memory_analysis()`. What the compiler refuses here costs
no chip time. Nothing runs, so these say nothing about results or times.

The topology is described only inside the module fixture: only one
process may load libtpu at a time, so describing it while a module is
imported would break the other test workers. Keep every such test in
this one file, so that one worker loads the library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from seaweedfs_tpu.models import geometry as gm
from seaweedfs_tpu.ops import rs_jax
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.storage.ec_files import DEFAULT_BATCH_SIZE

HBM_BYTES = 16 * 10**9  # one v5e chip
SLAB = DEFAULT_BATCH_SIZE  # bytes per shard row of one encode slab
K, M = 10, 4
LOST = (3, 11, 13)  # chip_smoke.py's lost set: one data, two parity


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described chip's compile would be written to the persistent cache
    # and could not be read back without the chip: keep the cache off
    cache_was = jax.config.jax_enable_compilation_cache
    log_was = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else libtpu logs under /tmp
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        # lint: allow-broad-except(no TPU compiler here: skip, with why)
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        if log_was is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_was


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < HBM_BYTES, mem


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("slabs", [1, 3, 32],
                         ids=["one-slab", "pipeline-depth", "widest-stack"])
def test_mxu_xla_encode_fits(one_chip, slabs):
    """The single-chip lane's encode (RSCodecJax.encode_parity, mxu-xla)
    at 1 slab, at the pipeline depth and at the widest stack the
    scheduler forms (DEFAULT_MAX_SLABS), width bucketed as served."""
    width = rs_jax._bucket(slabs * SLAB)
    compiled = rs_jax._encode_jit.lower(
        _sds((K, width), jnp.uint8, one_chip), K, M).compile()
    _fits(compiled)


# no output aliases the donated input (parity rows != data rows): XLA
# notes it, and still retires the buffer early, which is the point
@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_chip_lane_encode_fits(one_chip):
    """The per-chip lane's form: runtime bit-matrix operand, donated
    input, widest stack."""
    bits = rs_jax.parity_matrix_op(K, M)
    width = rs_jax._bucket(32 * SLAB)
    compiled = rs_jax._apply_matrix_jit_donated.lower(
        _sds(bits.shape, jnp.int8, one_chip),
        _sds((K, width), jnp.uint8, one_chip)).compile()
    _fits(compiled)


def test_fused_reconstruct_fits(one_chip):
    """3 lost shards rebuilt from the 11 survivors in one fused
    column-permuted matmul, at one slab."""
    present = tuple(i for i in range(K + M) if i not in LOST)
    missing, op = rs_jax.fused_reconstruct_stacked_op(K, M, present, K + M)
    assert missing == LOST
    compiled = rs_jax._apply_matrix_jit.lower(
        _sds(op.shape, jnp.int8, one_chip),
        _sds((len(present), SLAB), jnp.uint8, one_chip)).compile()
    _fits(compiled)


def test_fused_reconstruct_fits_at_rebuild_width(one_chip):
    """The rebuild's minimal-read form: the lost shards solved from the
    first k survivors (the `want` repair operand) over two slabs side by
    side, [k, 8 MiB], the width the archive rebuild dispatches."""
    reads = tuple(i for i in range(K + M) if i not in LOST)[:K]
    op = rs_jax.geom_stacked_op(gm.rs(K, M), reads, LOST)
    assert op.shape == (8 * len(LOST), 8 * K)
    compiled = rs_jax._apply_matrix_jit.lower(
        _sds(op.shape, jnp.int8, one_chip),
        _sds((K, 2 * SLAB), jnp.uint8, one_chip)).compile()
    _fits(compiled)


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices), (mesh_mod.STRIPE_AXIS,))


def test_mesh_column_encode_fits(mesh4):
    """ShardedCoder.encode_parity: the bitsliced shard_map with byte
    columns split over the 2x2 mesh, at the widest one dispatch takes
    (MESH_DISPATCH_COLS per device; wider work is split)."""
    op = rs_jax.parity_matrix_op(K, M)
    axis = mesh4.axis_names[0]
    width = 4 * mesh_mod.MESH_DISPATCH_COLS
    compiled = mesh_mod._apply_sharded.lower(
        _sds(op.shape, jnp.int8, NamedSharding(mesh4, P())),
        _sds((K, width), jnp.uint8, NamedSharding(mesh4, P(None, axis))),
        mesh4, axis).compile()
    _fits(compiled)


def test_mesh_vsharded_rebuild_fits(mesh4):
    """The V-sharded rebuild: whole survivor slabs [V, P, B] sharded over
    the mesh through the fused reconstruct operand, one slab per chip
    per dispatch as ShardedCoder._apply_vstack sends them."""
    present = tuple(i for i in range(K + M) if i not in LOST)
    _, op = rs_jax.fused_reconstruct_stacked_op(K, M, present, K + M)
    axis = mesh4.axis_names[0]
    v = 4
    compiled = mesh_mod._apply_stacked_vsharded.lower(
        _sds(op.shape, jnp.int8, NamedSharding(mesh4, P())),
        _sds((v, len(present), SLAB), jnp.uint8,
             NamedSharding(mesh4, P(axis, None, None))),
        mesh4, axis).compile()
    _fits(compiled)


def test_mesh_parity_probe_fits(mesh4):
    """ShardedCoder.parity_probe: the pmax collective over the mesh."""
    op = rs_jax.parity_matrix_op(K, M)
    axis = mesh4.axis_names[0]
    compiled = mesh_mod._parity_probe.lower(
        _sds(op.shape, jnp.int8, NamedSharding(mesh4, P())),
        _sds((K + M, 4 * mesh_mod.MESH_DISPATCH_COLS), jnp.uint8,
             NamedSharding(mesh4, P(None, axis))),
        mesh4, axis, K).compile()
    assert "all-reduce" in compiled.as_text()
    _fits(compiled)
