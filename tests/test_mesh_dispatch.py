"""Multi-chip sharded EC dispatch (ISSUE 5): V-axis lanes with
device-affine flushing.

The load-bearing property is the same as ISSUE 3's: per-chip lanes are
allowed to change only WHERE dispatches run, never what they compute —
V-axis bit-identity is pinned against the single-chip scheduler path,
the rs_cpu oracle (and its vsharded mirror), and the frozen golden shard
hashes. On top of that: per-chip lane fairness under 8 concurrent
pipelines (no chip starves), survivor-set chip placement with LRU
eviction, demand-flush latency through a device-affine lane, and clean
shutdown with in-flight per-chip dispatches.

Runs on the forced 8-device host platform (tests/conftest.py sets
--xla_force_host_platform_device_count=8).
"""

import hashlib
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import new_coder
from seaweedfs_tpu.ops import dispatch
from seaweedfs_tpu.ops.rs_cpu import RSCodecCPU
from seaweedfs_tpu.parallel.mesh import (ShardedCoder, device_count,
                                         local_devices, make_mesh)
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.storage.ec_locate import Geometry
from seaweedfs_tpu.utils import stats

TEST_GEO = Geometry(large_block=10000, small_block=100)


@pytest.fixture(autouse=True)
def _clean_schedulers():
    yield
    dispatch.shutdown_all()
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ec-dispatch") and t.is_alive()], \
        "leaked ec-dispatch flusher thread"


def _mesh_coder(chips: int | None = None):
    """A ShardedCoder over every device, or over the first `chips` (4:
    the devices a v5e 2x2 host ships)."""
    if device_count() < max(2, chips or 0):
        pytest.skip("needs the forced multi-device host platform")
    if chips is None:
        return ShardedCoder(10, 4)
    return ShardedCoder(10, 4, mesh=make_mesh(local_devices()[:chips]))


# -- V-axis shard_map variants: bit-identity --------------------------------


@pytest.mark.parametrize("v", [8, 11, 16, 3])
def test_vsharded_encode_stacked_bit_identity(v):
    """encode_parity_stacked with the V axis sharded across chips (v >=
    chips; v=3 exercises the column-split fallback) == per-slab rs_cpu,
    and == the CPU mirror of the exact per-chip partitioning."""
    coder = _mesh_coder()
    cpu = RSCodecCPU(10, 4)
    rng = np.random.default_rng(31)
    stack = rng.integers(0, 256, (v, 10, 257), dtype=np.uint8)
    got = np.asarray(coder.encode_parity_stacked(stack))
    want = np.stack([np.asarray(cpu.encode_parity(s)) for s in stack])
    assert got.shape == (v, 4, 257)
    assert np.array_equal(got, want)
    mirror = cpu.encode_parity_stacked_vsharded(stack, coder._n)
    assert np.array_equal(mirror, want)


def test_vsharded_encode_ragged_widths_zero_padding():
    """Ragged slab tails ride zero-padded columns through the V-sharded
    launch exactly as they do through the column split."""
    coder = _mesh_coder()
    cpu = RSCodecCPU(10, 4)
    rng = np.random.default_rng(32)
    widths = [512, 100, 37, 512, 9, 300, 64, 200, 411]
    bmax = max(widths)
    stack = np.zeros((len(widths), 10, bmax), dtype=np.uint8)
    slabs = []
    for i, w in enumerate(widths):
        s = rng.integers(0, 256, (10, w), dtype=np.uint8)
        stack[i, :, :w] = s
        slabs.append(s)
    out = np.asarray(coder.encode_parity_stacked(stack))
    for i, (w, s) in enumerate(zip(widths, slabs)):
        assert np.array_equal(out[i][:, :w],
                              np.asarray(cpu.encode_parity(s))), i
        assert not out[i][:, w:].any(), "zero columns must encode to zero"


@pytest.mark.parametrize("data_only", [False, True])
def test_vsharded_reconstruct_survivor_permutations(data_only):
    coder = _mesh_coder()
    cpu = RSCodecCPU(10, 4)
    rng = np.random.default_rng(33)
    data = rng.integers(0, 256, (10, 130), dtype=np.uint8)
    shards = np.asarray(cpu.encode(
        np.vstack([data, np.zeros((4, 130), np.uint8)])))
    for _ in range(4):
        ids = list(range(14))
        rng.shuffle(ids)
        pres = tuple(ids[:11])
        stk = np.stack([shards[i] for i in pres])
        vstack = np.stack([stk] * 9)  # ragged V vs the 8-device mesh
        m, rows = coder.reconstruct_stacked_vsharded(
            pres, vstack, data_only=data_only)
        m2, r2 = cpu.reconstruct_stacked(pres, stk, data_only=data_only)
        rows = np.asarray(rows)
        assert tuple(m) == tuple(m2)
        for j in range(9):
            assert np.array_equal(rows[j], np.asarray(r2)), j


def test_golden_shard_hashes_mesh_vsharded():
    """The frozen RS(10,4) fixture's shard bytes survive the V-sharded
    path (same golden as test_golden_identity pins for cpu/jax)."""
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_golden_identity import GOLDEN_SHARD_SHA256, _fixture

    coder = _mesh_coder()
    data = _fixture()
    stack = np.stack([data] * coder._n)  # every chip encodes the fixture
    parity = np.asarray(coder.encode_parity_stacked(stack))
    for slab in parity:
        shards = np.concatenate([data, slab], axis=0)
        got = [hashlib.sha256(s.tobytes()).hexdigest() for s in shards]
        assert got == GOLDEN_SHARD_SHA256


# -- scheduler: per-chip lanes ----------------------------------------------


@pytest.mark.parametrize("chips", [4, 8])
def test_scheduler_per_chip_encode_bit_identity_and_spread(chips):
    """Slabs submitted through the scheduler round-robin over per-chip
    lanes; every future's bytes match the rs_cpu oracle and every chip
    issued at least one batch."""
    coder = _mesh_coder(chips)
    cpu = RSCodecCPU(10, 4)
    sched = dispatch.EcDispatchScheduler(coder, window=0.05)
    try:
        rng = np.random.default_rng(34)
        b0 = stats.EC_DISPATCH_BATCHES.split_by("chip", lane="encode")
        slabs = [rng.integers(0, 256, (10, 64 + 8 * i), dtype=np.uint8)
                 for i in range(3 * coder._n)]
        futs = [sched.encode_parity(s) for s in slabs]
        for s, f in zip(slabs, futs):
            assert np.array_equal(np.asarray(f),
                                  np.asarray(cpu.encode_parity(s)))
        b1 = stats.EC_DISPATCH_BATCHES.split_by("chip", lane="encode")
        moved = {c: b1.get(c, 0) - b0.get(c, 0) for c in b1}
        for c in range(coder._n):
            assert moved.get(str(c), 0) > 0, f"chip {c} starved: {moved}"
    finally:
        sched.close()


def test_scheduler_vshard_env_gate_single_funnel():
    """SWFS_EC_DISPATCH_VSHARD=0 restores ISSUE 3's single stacked
    funnel: no per-chip lanes, bytes unchanged."""
    coder = _mesh_coder()
    cpu = RSCodecCPU(10, 4)
    os.environ["SWFS_EC_DISPATCH_VSHARD"] = "0"
    try:
        sched = dispatch.EcDispatchScheduler(coder, window=0.05)
        b0 = stats.EC_DISPATCH_BATCHES.split_by("chip", lane="encode")
        rng = np.random.default_rng(35)
        slabs = [rng.integers(0, 256, (10, 96), dtype=np.uint8)
                 for _ in range(12)]
        futs = [sched.encode_parity(s) for s in slabs]
        for s, f in zip(slabs, futs):
            assert np.array_equal(np.asarray(f),
                                  np.asarray(cpu.encode_parity(s)))
        b1 = stats.EC_DISPATCH_BATCHES.split_by("chip", lane="encode")
        assert b1.get("-", 0) > b0.get("-", 0), "single-funnel lane unused"
        assert all(b1.get(str(c), 0) == b0.get(str(c), 0)
                   for c in range(coder._n)), "chip lanes used while gated"
        sched.close()
    finally:
        os.environ.pop("SWFS_EC_DISPATCH_VSHARD", None)


@pytest.mark.parametrize("chips", [4, 8])
def test_per_chip_lane_fairness_under_8_pipelines(chips):
    """8 concurrent encode pipelines (one thread each, as 8 volumes
    encoding at once): every chip's dispatch counter moves — the fleet
    saturates every chip's queue instead of funnelling through one."""
    coder = _mesh_coder(chips)
    cpu = RSCodecCPU(10, 4)
    sched = dispatch.EcDispatchScheduler(coder, window=0.02)
    try:
        rng = np.random.default_rng(36)
        payloads = [
            [rng.integers(0, 256, (10, 128), dtype=np.uint8)
             for _ in range(6)]
            for _ in range(8)
        ]
        want = [[np.asarray(cpu.encode_parity(s)) for s in lane]
                for lane in payloads]
        b0 = stats.EC_DISPATCH_BATCHES.split_by("chip", lane="encode")
        errs = []
        barrier = threading.Barrier(8)

        def pipeline(i):
            try:
                barrier.wait()
                futs = [sched.encode_parity(s) for s in payloads[i]]
                for w, f in zip(want[i], futs):
                    assert np.array_equal(np.asarray(f), w)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ths = [threading.Thread(target=pipeline, args=(i,))
               for i in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert not errs, errs[0]
        b1 = stats.EC_DISPATCH_BATCHES.split_by("chip", lane="encode")
        for c in range(coder._n):
            assert b1.get(str(c), 0) > b0.get(str(c), 0), \
                f"chip {c} starved under the 8-pipeline load"
    finally:
        sched.close()


def test_reconstruct_survivor_set_chip_placement_lru():
    """Each survivor set is pinned to one chip (its fused decode matrix
    lives there); distinct sets spread over distinct chips; the
    assignment map is LRU-bounded."""
    coder = _mesh_coder()
    cpu = RSCodecCPU(10, 4)
    sched = dispatch.EcDispatchScheduler(coder, window=0.02)
    sched._rec_max = 4
    try:
        rng = np.random.default_rng(37)
        data = rng.integers(0, 256, (10, 96), dtype=np.uint8)
        shards = np.asarray(cpu.encode(
            np.vstack([data, np.zeros((4, 96), np.uint8)])))
        seen_chips = set()
        keys = []
        for drop in range(6):  # 6 distinct survivor sets > LRU cap 4
            pres = tuple(i for i in range(14)
                         if i not in (drop, drop + 4, drop + 8))[:11]
            stk = np.stack([shards[i] for i in pres])
            m, rows = sched.reconstruct_stacked(pres, stk).result()
            m2, r2 = cpu.reconstruct_stacked(pres, stk)
            assert tuple(m) == tuple(m2)
            assert np.array_equal(np.asarray(rows), np.asarray(r2))
            key = ("rec", sched.geom_id, pres, False, None)
            keys.append(key)
            with sched._cv:
                chip = sched._rec_chips.get(key)
            assert chip is not None
            seen_chips.add(chip)
        assert len(seen_chips) > 1, "survivor sets all pinned to one chip"
        with sched._cv:
            assert len(sched._rec_chips) <= 4, "rec-chip map not LRU-bounded"
            assert keys[0] not in sched._rec_chips, "oldest set not evicted"
        # a re-used (re-assigned) set still reconstructs bit-identically
        pres = keys[0][2]
        stk = np.stack([shards[i] for i in pres])
        m, rows = sched.reconstruct_stacked(pres, stk).result()
        m2, r2 = cpu.reconstruct_stacked(pres, stk)
        assert tuple(m) == tuple(m2)
        assert np.array_equal(np.asarray(rows), np.asarray(r2))
    finally:
        sched.close()


def test_big_uniform_reconstruct_batch_vshards_across_mesh():
    """A reconstruct lane whose demand-flushed backlog holds >= chips
    equal-width slabs (a rebuild pipeline's shape) dispatches through
    the V-sharded mesh variant instead of its single assigned chip —
    bytes identical slab for slab."""
    coder = _mesh_coder()
    cpu = RSCodecCPU(10, 4)
    sched = dispatch.EcDispatchScheduler(coder, window=30.0,
                                         max_slabs=64)
    try:
        rng = np.random.default_rng(39)
        data = rng.integers(0, 256, (10, 128), dtype=np.uint8)
        shards = np.asarray(cpu.encode(
            np.vstack([data, np.zeros((4, 128), np.uint8)])))
        pres = tuple(i for i in range(14) if i not in (1, 6, 12))
        stk = np.stack([shards[i] for i in pres])
        want = cpu.reconstruct_stacked(pres, stk)
        futs = [sched.reconstruct_stacked(pres, stk, copy=True)
                for _ in range(2 * coder._n)]  # > chips, uniform width
        # first result() demand-flushes the whole lane as ONE batch
        for f in futs:
            m, rows = f.result(timeout=30)
            assert tuple(m) == tuple(want[0])
            assert np.array_equal(np.asarray(rows), np.asarray(want[1]))
    finally:
        sched.close()


def test_demand_flush_latency_with_device_affine_lanes():
    """A consumer blocked on a per-chip lane demand-flushes THAT lane
    immediately — a 30s window never becomes serving latency."""
    import time

    coder = _mesh_coder()
    cpu = RSCodecCPU(10, 4)
    sched = dispatch.EcDispatchScheduler(coder, window=30.0)
    try:
        data = np.arange(640, dtype=np.uint8).reshape(10, 64)
        t0 = time.perf_counter()
        fut = sched.encode_parity(data)
        out = np.asarray(fut.result(timeout=10))
        assert time.perf_counter() - t0 < 5.0
        assert np.array_equal(out, np.asarray(cpu.encode_parity(data)))
    finally:
        sched.close()


def test_clean_shutdown_with_inflight_per_chip_dispatches():
    """close() with slabs queued across several chip lanes resolves every
    future (drain-then-join) and rejects new work afterwards."""
    coder = _mesh_coder()
    cpu = RSCodecCPU(10, 4)
    sched = dispatch.EcDispatchScheduler(coder, window=30.0)  # never fires
    rng = np.random.default_rng(38)
    slabs = [rng.integers(0, 256, (10, 80), dtype=np.uint8)
             for _ in range(2 * coder._n)]
    futs = [sched.encode_parity(s) for s in slabs]
    assert sched.pending() == len(slabs)
    depths = sched.chip_depths()
    assert sum(depths.values()) == len(slabs)
    assert len([c for c in depths if c != "-"]) == coder._n
    sched.close()
    for s, f in zip(slabs, futs):
        assert f.done()
        assert np.array_equal(np.asarray(f.result(timeout=1)),
                              np.asarray(cpu.encode_parity(s)))
    with pytest.raises(RuntimeError):
        sched.encode_parity(np.zeros((10, 8), np.uint8))
    sched.close()  # idempotent


def test_shutdown_all_idempotent():
    """shutdown_all twice (as atexit + Store.close teardown orders can
    produce) is a no-op the second time, and a broken scheduler in the
    set cannot stop the others from closing. (atexit registration itself
    happens at module import — ops/dispatch.py — and is not portably
    introspectable; idempotency is the property it depends on.)"""
    coder = RSCodecCPU(10, 4)
    sched = dispatch.scheduler_for(coder)
    np.asarray(sched.encode_parity(np.zeros((10, 16), np.uint8)))
    dispatch.shutdown_all()
    dispatch.shutdown_all()  # second call is a no-op, not an error
    assert sched.closed

    class _Broken(dispatch.EcDispatchScheduler):
        def close(self):
            raise RuntimeError("teardown bomb")

    boom = _Broken(RSCodecCPU(10, 4), window=0.01)
    healthy = dispatch.EcDispatchScheduler(RSCodecCPU(10, 4), window=0.01)
    dispatch.shutdown_all()  # must visit every scheduler despite the bomb
    assert healthy.closed
    dispatch.EcDispatchScheduler.close(boom)  # real cleanup


# -- pipeline golden safety over the mesh -----------------------------------


def test_generate_ec_files_bit_identical_vshard_on_off(tmp_path, monkeypatch):
    """The acceptance pin: .ec00-.ec13 bytes identical with per-chip
    lanes on and off, over the mesh-backed auto coder."""
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from test_ec_pipeline import _make_synthetic_volume

    outs = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("SWFS_EC_DISPATCH_VSHARD", mode)
        monkeypatch.setenv("SWFS_EC_MESH_VSHARD", mode)
        base = str(tmp_path / f"v{mode}")
        _make_synthetic_volume(base, seed=41)
        coder = new_coder(10, 4, "tpu")
        ec_files.generate_ec_files(base, coder, TEST_GEO, batch_size=50)
        dispatch.shutdown_all()
        outs[mode] = [
            open(TEST_GEO.shard_file_name(base, i), "rb").read()
            for i in range(14)
        ]
    for i in range(14):
        assert outs["0"][i] == outs["1"][i], f"shard {i} differs"


def test_store_close_twice_is_safe(tmp_path):
    """Satellite: Store.close() is idempotent — a double close neither
    re-closes volumes nor re-joins the dispatch flusher."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    st = Store([str(tmp_path)])
    v = st.add_volume(1)
    v.write_needle(Needle.create(1, 0xA, b"x" * 100))
    # attach a scheduler (as EC work would) so close exercises the join
    sched = dispatch.scheduler_for(st.coder)
    np.asarray(sched.encode_parity(np.zeros((10, 16), np.uint8)))
    st.close()
    st.close()  # must not hang or raise
    assert sched.closed
