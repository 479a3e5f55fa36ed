"""How the EC file pipeline fills a slab (storage/ec_files.py): concurrent
positional reads into recycled buffers, for the encode and the rebuild.

Shard bytes are checked against a plain encode of the .dat by the rs_cpu
oracle, laid out as the reference's encodeDatFile walks it
(ec_encoder.go:194-231): rows of large then small blocks, each of the k
blocks zero-filled past EOF. Volumes: a synthetic one whose last row is
ragged, one whose last row has blocks wholly past EOF, and the
reference's committed 1.dat fixture where it is present.
"""

import os
import queue
import shutil
import threading
import time
import types

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import new_coder
from seaweedfs_tpu.ops.rs_cpu import RSCodecCPU
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.storage.ec_locate import Geometry
from seaweedfs_tpu.utils import fanout, stats, trace

from .test_ec_pipeline import REF_FIXTURE, _make_synthetic_volume

GEO = Geometry(large_block=10000, small_block=100)
K, M = GEO.data_shards, GEO.parity_shards
DEPTH = ec_files.DEFAULT_PIPELINE_DEPTH


def _oracle_shards(dat: bytes, geo: Geometry = GEO) -> list[bytes]:
    """Every shard file's bytes, from the .dat and the rs_cpu oracle."""
    codec = RSCodecCPU(K, M)
    n_large, n_small = geo.row_counts(len(dat))
    shards = [bytearray() for _ in range(K + M)]
    processed = 0
    for block in [geo.large_block] * n_large + [geo.small_block] * n_small:
        rows = np.zeros((K, block), np.uint8)
        for i in range(K):
            chunk = dat[processed + block * i:processed + block * (i + 1)]
            rows[i, :len(chunk)] = np.frombuffer(chunk, np.uint8)
        parity = np.asarray(codec.encode_parity(rows))
        for i in range(K):
            shards[i] += rows[i].tobytes()
        for j in range(M):
            shards[K + j] += parity[j].tobytes()
        processed += block * K
    return [bytes(s) for s in shards]


def _volume(kind: str, base: str) -> bytes:
    """Write <base>.dat (and .idx) of one kind; return the .dat bytes."""
    if kind == "ragged":
        _make_synthetic_volume(base, n_needles=12, seed=21)
    elif kind == "past_eof":
        # 1,234 bytes: the second small row holds 234 of its 1,000, so its
        # block 2 is ragged and blocks 3..9 lie wholly past EOF
        rng = np.random.default_rng(22)
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, 1234, dtype=np.uint8).tobytes())
    else:
        if not os.path.exists(REF_FIXTURE + ".dat"):
            pytest.skip("reference fixture absent")
        shutil.copy(REF_FIXTURE + ".dat", base + ".dat")
    with open(base + ".dat", "rb") as f:
        return f.read()


def _shards(base: str, ids=range(K + M)) -> dict[int, bytes]:
    out = {}
    for i in ids:
        with open(GEO.shard_file_name(base, i), "rb") as f:
            out[i] = f.read()
    return out


@pytest.fixture
def fresh_read_pool(monkeypatch):
    """A fresh `ec_read` pool for the test (sized by the test's
    cpu_count), shut down after it; the process's own pool is put back."""
    old = fanout._executors.pop("ec_read", None)
    yield
    pool = fanout._executors.pop("ec_read", None)
    if pool is not None:
        pool.shutdown(wait=True)
    if old is not None:
        fanout._executors["ec_read"] = old


def _short_preads(monkeypatch, cap: int = 37):
    """Every positional read returns at most `cap` bytes, as 9p may."""
    real = os.preadv

    def short(fd, buffers, offset):
        return real(fd, [memoryview(buffers[0])[:cap]], offset)

    monkeypatch.setattr(os, "preadv", short)


class _Sinks:
    """A sink set that keeps every row it is handed, by shard offset."""

    def __init__(self):
        self.rows: dict[int, dict[int, bytes]] = {}
        self._lock = threading.Lock()

    def put(self, shard_id, offset, row, nbytes):
        with self._lock:
            self.rows.setdefault(shard_id, {})[offset] = bytes(
                memoryview(row)[:nbytes])

    def shard(self, shard_id) -> bytes:
        parts = self.rows.get(shard_id, {})
        return b"".join(parts[o] for o in sorted(parts))


@pytest.mark.parametrize("sinks", [False, True], ids=["files", "sinks"])
@pytest.mark.parametrize("short", [False, True], ids=["full", "short"])
@pytest.mark.parametrize("cpus", [1, 4], ids=["1stream", "4streams"])
@pytest.mark.parametrize("kind", ["ragged", "past_eof", "reference"])
def test_encode_shards_match_oracle(tmp_path, monkeypatch, fresh_read_pool,
                                    kind, cpus, short, sinks):
    """Shards are the oracle's bytes with one read stream or several,
    when every positional read returns short, and through `sinks`."""
    base = str(tmp_path / "v")
    dat = _volume(kind, base)
    monkeypatch.setattr(os, "cpu_count", lambda: cpus)
    if short:
        _short_preads(monkeypatch)
    sink = _Sinks() if sinks else None
    ec_files.generate_ec_files(base, new_coder(K, M, "cpu"), GEO,
                               batch_size=50, sinks=sink)
    want = _oracle_shards(dat)
    got = _shards(base)
    for i in range(K + M):
        assert got[i] == want[i], f"shard {i} differs from the oracle"
        if sink is not None:
            assert sink.shard(i) == want[i], f"sink of shard {i} differs"


class _LateScheduler:
    """A dispatch scheduler that keeps each slab's survivor buffer by
    reference, without a copy, and reads it only when the coordinator
    asks for the rows, after a pause: a buffer recycled before its
    slab's rows are host bytes would be refilled under it."""

    def __init__(self, coder):
        self._coder = coder

    def reconstruct_stacked(self, present_ids, stacked, want=None):
        return _LateRows(self._coder, present_ids, stacked, want)


class _LateRows:
    def __init__(self, coder, present_ids, stacked, want):
        self._args = (coder, present_ids, stacked, want)
        self._rows = None

    def __getitem__(self, shard_id):
        if self._rows is None:
            coder, present_ids, stacked, want = self._args
            time.sleep(0.02)
            mids, rows = coder.reconstruct_stacked(present_ids, stacked,
                                                   want=want)
            self._rows = dict(zip(mids, rows))
        return self._rows[shard_id]


@pytest.mark.parametrize("short", [False, True], ids=["full", "short"])
@pytest.mark.parametrize("cpus", [1, 4], ids=["1stream", "4streams"])
@pytest.mark.parametrize("dispatch", ["1", "0", "late"],
                         ids=["sched", "direct", "late"])
@pytest.mark.parametrize("coder", ["tpu", "cpu"])
def test_rebuild_reuses_every_buffer_and_matches(
        tmp_path, monkeypatch, fresh_read_pool, coder, dispatch, cpus,
        short):
    """A rebuild over more than twice DEFAULT_PIPELINE_DEPTH + 2 slabs
    takes its survivor buffers from the pool in turn, so each buffer
    fills at least two slabs; the rebuilt shards equal those they
    replace, and the oracle's, also where the scheduler reads a buffer
    late (a buffer goes back to the pool only once its rows are back)."""
    monkeypatch.setenv("SWFS_EC_DISPATCH", "0" if dispatch == "0" else "1")
    base = str(tmp_path / "r")
    dat = _volume("ragged", base)
    c = new_coder(K, M, coder)
    ec_files.generate_ec_files(base, c, GEO, batch_size=100)
    lost = [0, 5, 13]
    before = _shards(base, lost)
    for i in lost:
        os.remove(GEO.shard_file_name(base, i))
    monkeypatch.setattr(os, "cpu_count", lambda: cpus)
    if short:
        _short_preads(monkeypatch)
    fills: list[int] = []
    real_rows = ec_files._pread_rows

    def rows(reads, streams, phases):
        fills.append(id(reads[0][2].obj.base))  # the [P, batch] buffer
        return real_rows(reads, streams, phases)

    monkeypatch.setattr(ec_files, "_pread_rows", rows)
    if dispatch == "late":
        monkeypatch.setattr(ec_files.dispatch, "maybe_scheduler",
                            _LateScheduler)
    st: dict = {}
    batch = 64
    assert ec_files.rebuild_ec_files(base, c, GEO, batch_size=batch,
                                     stats=st) == lost
    assert st["slabs"] == len(fills) >= 2 * (DEPTH + 2)
    uses = {b: fills.count(b) for b in set(fills)}
    assert len(uses) == DEPTH + 2 and min(uses.values()) >= 2, uses
    after = _shards(base, lost)
    want = _oracle_shards(dat)
    for i in lost:
        assert after[i] == before[i] == want[i], f"rebuilt shard {i} differs"


class _LateEncodeCoder:
    """An encode coder over the rs_cpu oracle whose parity reads the
    slab it was handed only when asked for as host bytes, after a pause:
    a device that has not read its input yet when the slab's data rows
    are on disk. It has no stacked entry, so the scheduler hands it
    each slab as submitted, batched or not."""

    def __init__(self):
        self.data_shards, self.parity_shards = K, M
        self._oracle = RSCodecCPU(K, M)

    def encode_parity(self, data):
        return _LateParity(self._oracle, data)


class _LateParity:
    def __init__(self, oracle, data):
        self._oracle, self._data = oracle, data

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.01)
        return np.asarray(self._oracle.encode_parity(self._data), dtype)


def _large_and_small(base: str) -> bytes:
    """A .dat of large and small rows whose last slab is ragged."""
    _make_synthetic_volume(base, n_needles=80, seed=24)
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    n_large, n_small = GEO.row_counts(len(dat))
    assert n_large >= 1 and n_small >= 2
    assert len(dat) % GEO.small_block
    return dat


def _encode_within(timeout_s: float, *args, **kw) -> ec_files.EncodeStats:
    """generate_ec_files on a thread of its own: a pipeline that
    deadlocks fails the test after `timeout_s` instead of hanging it."""
    out: list = []

    def run():
        try:
            out.append(ec_files.generate_ec_files(*args, **kw))
        except BaseException as e:  # surfaced by the assert below
            out.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout_s)
    assert not t.is_alive(), f"encode still running after {timeout_s} s"
    assert isinstance(out[0], ec_files.EncodeStats), out
    return out[0]


class _UnboundedQueue(queue.Queue):
    def __init__(self, maxsize: int = 0):
        super().__init__()


@pytest.mark.parametrize("work_queue", ["bounded", "unbounded"])
@pytest.mark.parametrize("depth", [1, 3])
def test_encode_buffer_held_until_parity_is_host_bytes(tmp_path, monkeypatch,
                                                       depth, work_queue):
    """Through a real dispatch scheduler, a coder that reads each slab
    only once its parity is asked for still gives the oracle's shards:
    a slab's read buffer is not refilled before its parity is host
    bytes, whatever the pipeline depth. With the pipeline's queues
    unbounded the reader runs as far ahead as the buffer pool lets it,
    so the buffer's hold is the only thing that keeps it from refilling
    a slab the coder has not read yet."""
    monkeypatch.setenv("SWFS_EC_DISPATCH", "1")
    if work_queue == "unbounded":
        monkeypatch.setattr(ec_files, "queue", types.SimpleNamespace(
            Queue=_UnboundedQueue, Empty=queue.Empty, Full=queue.Full))
    base = str(tmp_path / "h")
    dat = _large_and_small(base)
    coder = _LateEncodeCoder()
    sched = ec_files.dispatch.scheduler_for(coder)
    try:
        _encode_within(120, base, coder, GEO, batch_size=5000,
                       pipeline_depth=depth)
    finally:
        sched.close()
    want = _oracle_shards(dat)
    got = _shards(base)
    for i in range(K + M):
        assert got[i] == want[i], f"shard {i} differs from the oracle"


def test_encode_submits_pool_buffers_without_copy(tmp_path, monkeypatch):
    """The encode pipeline hands the scheduler C-contiguous views of its
    pool buffers and the scheduler copies none of them; a scrub-style
    submit, with the default, still snapshots its slab and counts the
    bytes in `SeaweedFS_ec_dispatch_submit_copied_bytes{lane}`."""
    monkeypatch.setenv("SWFS_EC_DISPATCH", "1")
    base = str(tmp_path / "n")
    dat = _large_and_small(base)
    coder = new_coder(K, M, "cpu")
    sched = ec_files.dispatch.scheduler_for(coder)
    submitted: list[np.ndarray] = []
    real_submit = sched._submit

    def submit(key, data, chip=None):
        submitted.append(data)
        return real_submit(key, data, chip=chip)

    monkeypatch.setattr(sched, "_submit", submit)
    pool: dict[int, np.ndarray] = {}
    real_rows = ec_files._pread_rows

    def rows(reads, streams, phases):
        buf = reads[0][2].obj.base  # row 0 of a slab: never past EOF here
        pool[id(buf)] = buf
        return real_rows(reads, streams, phases)

    monkeypatch.setattr(ec_files, "_pread_rows", rows)
    copied0 = stats.EC_DISPATCH_SUBMIT_COPIED.value(lane="encode")
    try:
        st = _encode_within(120, base, coder, GEO, batch_size=5000)
        assert stats.EC_DISPATCH_SUBMIT_COPIED.value(lane="encode") == copied0
        assert len(pool) == DEPTH + 2
        assert len(submitted) == st.batches > len(pool)
        for slab in submitted:
            assert slab.flags.c_contiguous
            assert any(np.shares_memory(slab, b) for b in pool.values())
        want = _oracle_shards(dat)
        got = _shards(base)
        for i in range(K + M):
            assert got[i] == want[i], f"shard {i} differs from the oracle"

        scrub = np.random.default_rng(25).integers(0, 256, (K, 300),
                                                   dtype=np.uint8)
        fut = sched.encode_parity(scrub)
        assert stats.EC_DISPATCH_SUBMIT_COPIED.value(lane="encode") \
            == copied0 + scrub.nbytes
        assert not np.shares_memory(submitted[-1], scrub)
        assert np.array_equal(np.asarray(fut),
                              RSCodecCPU(K, M).encode_parity(scrub))
    finally:
        sched.close()


POOL_THREAD = "swfs-fanout-ec_read"


def _record_preads(monkeypatch, hold_s: float = 0.0):
    """Wrap os.preadv: record (thread name, fd, offset) of every call and
    the most calls ever in flight at once on the read pool's workers;
    `hold_s` keeps each call in flight that long."""
    real = os.preadv
    calls: list[tuple[str, int, int]] = []
    lock = threading.Lock()
    live = {"now": 0, "max": 0}

    def rec(fd, buffers, offset):
        name = threading.current_thread().name
        pooled = name.startswith(POOL_THREAD)
        with lock:
            calls.append((name, fd, offset))
            if pooled:
                live["now"] += 1
                live["max"] = max(live["max"], live["now"])
        try:
            if hold_s:
                time.sleep(hold_s)
            return real(fd, buffers, offset)
        finally:
            if pooled:
                with lock:
                    live["now"] -= 1

    monkeypatch.setattr(os, "preadv", rec)
    return calls, live


def test_one_cpu_reads_rows_serially_in_order(tmp_path, monkeypatch,
                                              fresh_read_pool):
    """On a one-CPU host each slab is one stream: the reader thread
    itself reads the rows, in today's order (rows of a slab in shard
    order, slabs in file order), and the rebuild reads its survivors in
    plan order."""
    base = str(tmp_path / "o")
    dat = _volume("past_eof", base)
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    calls, _ = _record_preads(monkeypatch)
    batch = 50
    ec_files.generate_ec_files(base, new_coder(K, M, "cpu"), GEO,
                               batch_size=batch)
    n_large, n_small = GEO.row_counts(len(dat))
    assert n_large == 0
    order = [GEO.small_block * K * r + GEO.small_block * i + b
             for r in range(n_small)
             for b in range(0, GEO.small_block, batch)
             for i in range(K)
             if GEO.small_block * K * r + GEO.small_block * i + b < len(dat)]
    assert [off for _, _, off in calls] == order
    assert {name for name, _, _ in calls} == {"ec-encode-reader"}

    calls.clear()
    for i in (2, 11):
        os.remove(GEO.shard_file_name(base, i))
    ec_files.rebuild_ec_files(base, new_coder(K, M, "cpu"), GEO,
                              batch_size=batch)
    shard_size = GEO.shard_size(len(dat))
    slabs = -(-shard_size // batch)
    assert {name for name, _, _ in calls} == {"ec-rebuild-reader"}
    assert [off for _, _, off in calls] == [
        s * batch for s in range(slabs) for _ in range(K)]
    fds = [fd for _, fd, _ in calls[:K]]
    assert len(set(fds)) == K and [fd for _, fd, _ in calls] == fds * slabs


def test_two_pipelines_share_the_read_pool_cap(tmp_path, monkeypatch,
                                               fresh_read_pool):
    """Two encodes and a rebuild running at once ask for 3 streams a slab
    each, and never hold more read workers than the shared pool's cap of
    one per CPU (3 here). (A slab with one row left to read past EOF is
    one stream, read on its pipeline's own reader thread.)"""
    monkeypatch.setattr(os, "cpu_count", lambda: 3)
    bases = []
    for n in range(3):
        base = str(tmp_path / f"p{n}")
        _make_synthetic_volume(base, n_needles=6, seed=30 + n)
        bases.append(base)
    coder = new_coder(K, M, "cpu")
    ec_files.generate_ec_files(bases[2], coder, GEO, batch_size=100)
    os.remove(GEO.shard_file_name(bases[2], 4))
    calls, live = _record_preads(monkeypatch, hold_s=0.002)
    errors = []

    def run(fn, *args):
        try:
            fn(*args)
        except BaseException as e:  # surfaced by the assert below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(
        ec_files.generate_ec_files, b, coder, GEO, 100)) for b in bases[:2]]
    threads.append(threading.Thread(target=run, args=(
        ec_files.rebuild_ec_files, bases[2], coder, GEO, 100)))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    cap = fanout.executor("ec_read")._max_workers
    assert cap == 3
    workers = {name for name, _, _ in calls if name.startswith(POOL_THREAD)}
    assert 2 <= live["max"] <= cap
    assert 2 <= len(workers) <= cap
    assert {name for name, _, _ in calls} - workers <= {
        "ec-encode-reader", "ec-rebuild-reader"}


@pytest.mark.parametrize("cpus", [1, 4, 16], ids=["1cpu", "4cpus", "16cpus"])
def test_read_streams_counter_and_span_attr(tmp_path, monkeypatch,
                                            fresh_read_pool, cpus):
    """`SeaweedFS_ec_pipeline_read_streams{op}` grows by the streams of
    each slab, and each `ec.<op>.read` span carries them as `streams`:
    min(rows read, CPUs) for the encode (fewer where rows lie past EOF),
    min(survivors, CPUs) for the rebuild."""
    base = str(tmp_path / "c")
    dat = _volume("past_eof", base)
    monkeypatch.setattr(os, "cpu_count", lambda: cpus)
    coder = new_coder(K, M, "cpu")

    def run(op, fn, *args, **kw):
        s0 = stats.EC_PIPELINE_READ_STREAMS.value(op=op)
        n0 = stats.EC_PIPELINE_SLABS.value(op=op)
        with trace.span(f"test.{op}") as root:
            fn(*args, **kw)
        reads = [s for s in trace.STORE.trace(root.trace_id)
                 if s["name"] == f"ec.{op}.read"]
        assert len(reads) == stats.EC_PIPELINE_SLABS.value(op=op) - n0
        return [s["attrs"]["streams"] for s in reads], \
            stats.EC_PIPELINE_READ_STREAMS.value(op=op) - s0

    batch = 50
    streams, counted = run("encode", ec_files.generate_ec_files, base,
                           coder, GEO, batch_size=batch)
    rows = [sum(1 for i in range(K)
                if GEO.small_block * K * r + GEO.small_block * i + b
                < len(dat))
            for r in range(GEO.row_counts(len(dat))[1])
            for b in range(0, GEO.small_block, batch)]
    assert sorted(streams) == sorted(min(n, cpus) for n in rows)
    assert counted == sum(streams)

    os.remove(GEO.shard_file_name(base, 7))
    streams, counted = run("rebuild", ec_files.rebuild_ec_files, base,
                           coder, GEO, batch_size=batch)
    assert streams and set(streams) == {min(K, cpus)}
    assert counted == sum(streams)
