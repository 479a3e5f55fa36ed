"""Per-chip submission locks in the EC dispatch scheduler, on the forced
multi-device CPU mesh (tests/conftest.py), with the lock witness armed.

A chip-pinned lane launches single-device jits only, so its flush takes
its own chip's lock: one chip's pack and launch never waits for
another's, and `SeaweedFS_ec_dispatch_overlapped_batches` counts the
batches whose submission started while another chip's was under way. A
launch that may span the mesh (the V-stacked encode of a non-chip lane,
the V-sharded reconstruct of a big backlog) takes every lock, so it
neither starts under a chip submission nor lets one start under it.
Bytes never depend on which of the two happened.
"""

import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import dispatch
from seaweedfs_tpu.ops.rs_cpu import RSCodecCPU
from seaweedfs_tpu.parallel.mesh import ShardedCoder, local_devices, make_mesh
from seaweedfs_tpu.utils import locks, stats

CHIPS = 4
HOLD_S = 30.0  # how long a held coder call waits for its release
QUIET_S = 0.3  # how long a blocked flush is watched for not starting


@pytest.fixture(autouse=True)
def _clean_schedulers():
    yield
    dispatch.shutdown_all()


def _coder(chips: int = CHIPS) -> ShardedCoder:
    if len(local_devices()) < chips:
        pytest.skip("needs the forced multi-device host platform")
    return ShardedCoder(10, 4, mesh=make_mesh(local_devices()[:chips]))


class _Gate:
    """Wraps one coder entry point: records each call as it enters, and
    holds the calls `hold` picks until `release` is set."""

    def __init__(self, fn, hold=lambda *a: True):
        self.fn = fn
        self.hold = hold
        self.entered = []
        self.started = threading.Event()
        self.release = threading.Event()

    def __call__(self, *args, **kw):
        self.entered.append(args)
        self.started.set()
        if self.hold(*args):
            assert self.release.wait(HOLD_S), "held coder call not released"
        return self.fn(*args, **kw)


def _flush_in_thread(sched, key) -> threading.Thread:
    t = threading.Thread(target=sched._demand_flush, args=(key,))
    t.start()
    return t


def _overlapped(chip: str) -> float:
    return stats.EC_DISPATCH_OVERLAPPED.value(lane="encode", chip=chip)


def _slabs(seed: int, n: int, width: int = 96) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (10, width), dtype=np.uint8)
            for _ in range(n)]


@pytest.mark.parametrize("held", [0, 2])
def test_chip_flush_completes_while_another_chip_is_held(held):
    """Chip `held`'s coder call is parked on an Event; the next chip's
    flush runs to its end meanwhile, and counts as overlapped."""
    coder = _coder()
    other = (held + 1) % CHIPS
    chips = coder.placement_devices()
    gate = _Gate(coder.encode_parity_on,
                 hold=lambda data, device: chips.index(device) == held)
    coder.encode_parity_on = gate
    sched = dispatch.EcDispatchScheduler(coder, window=HOLD_S)
    cpu = RSCodecCPU(10, 4)
    slabs = _slabs(51 + held, CHIPS)
    futs = [sched.encode_parity(s) for s in slabs]  # one slab a chip
    keys = [("enc", sched.geom_id, c) for c in range(CHIPS)]
    t_held = _flush_in_thread(sched, keys[held])
    try:
        assert gate.started.wait(HOLD_S)
        before = _overlapped(str(other))
        t_other = _flush_in_thread(sched, keys[other])
        t_other.join(HOLD_S)
        assert not t_other.is_alive(), \
            f"chip {other}'s flush waited for chip {held}'s submission"
        assert futs[other].done() and not futs[held].done()
        assert _overlapped(str(other)) == before + 1
    finally:
        gate.release.set()
        t_held.join(HOLD_S)
    sched.flush()
    for s, f in zip(slabs, futs):
        assert np.array_equal(np.asarray(f), np.asarray(cpu.encode_parity(s)))


def _mesh_lane(kind, sched, coder, monkeypatch):
    """Queue a lane whose flush launches across the mesh; returns (its
    key, the gated mesh entry point, a check of its futures' bytes)."""
    cpu = RSCodecCPU(10, 4)
    if kind == "vstack_encode":
        # a non-chip encode lane: the V-stacked launch of a mesh coder
        gate = _Gate(coder.encode_parity_stacked)
        coder.encode_parity_stacked = gate
        monkeypatch.setenv("SWFS_EC_DISPATCH_VSHARD", "0")
        slabs = _slabs(61, 2)
        futs = [sched.encode_parity(s) for s in slabs]
        want = [np.asarray(cpu.encode_parity(s)) for s in slabs]

        def check():
            for w, f in zip(want, futs):
                assert np.array_equal(np.asarray(f.result(HOLD_S)), w)
        return ("enc", sched.geom_id), gate, check
    # a pinned reconstruct lane whose backlog fills the mesh
    gate = _Gate(coder.reconstruct_stacked_vsharded)
    coder.reconstruct_stacked_vsharded = gate
    data = _slabs(62, 1, width=128)[0]
    shards = np.asarray(cpu.encode(np.vstack([data, np.zeros((4, 128),
                                                             np.uint8)])))
    pres = tuple(i for i in range(14) if i not in (1, 6, 12))
    stk = np.stack([shards[i] for i in pres])
    futs = [sched.reconstruct_stacked(pres, stk, copy=True)
            for _ in range(CHIPS)]
    m_want, r_want = cpu.reconstruct_stacked(pres, stk)

    def check():
        for f in futs:
            m, rows = f.result(HOLD_S)
            assert tuple(m) == tuple(m_want)
            assert np.array_equal(np.asarray(rows), np.asarray(r_want))
    return ("rec", sched.geom_id, pres, False, None), gate, check


@pytest.mark.parametrize("first", ["chip", "mesh"])
@pytest.mark.parametrize("kind", ["vstack_encode", "vsharded_reconstruct"])
def test_mesh_wide_launch_excludes_chip_submissions(kind, first,
                                                    monkeypatch):
    """A mesh-wide launch does not start while a chip submission holds
    its lock, and a chip submission does not start under a mesh-wide
    one. The mesh-wide flush takes the locks in index order, which the
    witness records as legal nesting."""
    coder = _coder()
    chip_gate = _Gate(coder.encode_parity_on)
    coder.encode_parity_on = chip_gate
    sched = dispatch.EcDispatchScheduler(coder, window=HOLD_S)
    cpu = RSCodecCPU(10, 4)
    chip_slab = _slabs(63, 1)[0]
    chip_fut = sched.encode_parity(chip_slab)  # chip 0's lane
    chip_key = ("enc", sched.geom_id, 0)
    mesh_key, mesh_gate, check_mesh = _mesh_lane(kind, sched, coder,
                                                 monkeypatch)
    if first == "chip":
        holder, holder_key, waiter, waiter_key = \
            chip_gate, chip_key, mesh_gate, mesh_key
    else:
        holder, holder_key, waiter, waiter_key = \
            mesh_gate, mesh_key, chip_gate, chip_key
    waiter.hold = lambda *a: False
    t_holder = _flush_in_thread(sched, holder_key)
    t_waiter = None
    try:
        assert holder.started.wait(HOLD_S)
        t_waiter = _flush_in_thread(sched, waiter_key)
        t_waiter.join(QUIET_S)
        assert t_waiter.is_alive() and not waiter.entered, \
            f"the {'mesh' if first == 'chip' else 'chip'} launch started " \
            f"under the other's submission"
    finally:
        holder.release.set()
        t_holder.join(HOLD_S)
        if t_waiter is not None:
            t_waiter.join(HOLD_S)
    assert waiter.entered and holder.entered
    check_mesh()
    assert np.array_equal(np.asarray(chip_fut.result(HOLD_S)),
                          np.asarray(cpu.encode_parity(chip_slab)))
    if locks.witness_enabled():
        edges = locks.observed_edges()
        assert "dispatch.chip0" in edges.get("dispatch.mu", set())
        for i in range(CHIPS - 1):
            assert f"dispatch.chip{i + 1}" in edges.get(
                f"dispatch.chip{i}", set())


@pytest.mark.parametrize("chips", [4, 8])
def test_parity_identical_overlapped_and_serialized(chips):
    """The same slabs through two schedulers: once with every chip's
    flush overlapping a parked chip 0, once one lane at a time from one
    thread. Parity bytes match each other and the rs_cpu oracle; only
    the first run counts overlapped batches."""
    coder = _coder(chips)
    cpu = RSCodecCPU(10, 4)
    slabs = _slabs(70 + chips, 3 * chips, width=160)
    devices = coder.placement_devices()
    real = coder.encode_parity_on

    # overlapped: chip 0 parked until every other chip's flush is done
    gate = _Gate(real, hold=lambda data, device: devices.index(device) == 0)
    coder.encode_parity_on = gate
    sched = dispatch.EcDispatchScheduler(coder, window=HOLD_S)
    futs = [sched.encode_parity(s) for s in slabs]
    total0 = stats.EC_DISPATCH_OVERLAPPED.value(lane="encode")
    t0 = _flush_in_thread(sched, ("enc", sched.geom_id, 0))
    try:
        assert gate.started.wait(HOLD_S)
        others = [_flush_in_thread(sched, ("enc", sched.geom_id, c))
                  for c in range(1, chips)]
        for t in others:
            t.join(HOLD_S)
            assert not t.is_alive()
    finally:
        gate.release.set()
        t0.join(HOLD_S)
    assert stats.EC_DISPATCH_OVERLAPPED.value(lane="encode") \
        - total0 >= chips - 1
    overlapped = [np.asarray(f.result(HOLD_S)) for f in futs]
    sched.close()

    # serialized: one thread flushes every lane in turn
    coder.encode_parity_on = real
    sched = dispatch.EcDispatchScheduler(coder, window=HOLD_S)
    futs = [sched.encode_parity(s) for s in slabs]
    total1 = stats.EC_DISPATCH_OVERLAPPED.value(lane="encode")
    sched.flush()
    serialized = [np.asarray(f.result(HOLD_S)) for f in futs]
    assert stats.EC_DISPATCH_OVERLAPPED.value(lane="encode") == total1
    sched.close()

    for s, a, b in zip(slabs, overlapped, serialized):
        assert np.array_equal(a, b)
        assert np.array_equal(a, np.asarray(cpu.encode_parity(s)))


def test_single_device_coder_keeps_one_lock():
    """A coder with one device has no chip locks: every flush takes the
    scheduler's one lock, as before the chips had their own."""
    sched = dispatch.EcDispatchScheduler(RSCodecCPU(10, 4), window=HOLD_S)
    fut = sched.encode_parity(_slabs(80, 1)[0])
    assert sched._chip_mu == [] and sched._chips == []
    assert sched._mesh_wide(("enc", sched.geom_id), None)
    np.asarray(fut)
    sched.close()
