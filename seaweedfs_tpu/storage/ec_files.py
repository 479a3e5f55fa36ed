"""EC shard-file pipelines: encode a .dat into .ecNN shards, rebuild missing
shards, and decode shards back into a .dat/.idx.

Behavioral equivalent of the reference's
weed/storage/erasure_coding/ec_encoder.go (WriteEcFiles, RebuildEcFiles,
encodeDatFile, rebuildEcFiles) and ec_decoder.go (WriteDatFile,
WriteIdxFileFromEcIndex, FindDatFileSize) — with a TPU-first execution
design: where the Go path is strictly serial (256KB read -> SIMD encode ->
14 writes, ec_encoder.go:57,162-192), we stream large slabs and overlap host
file I/O with device compute. JAX dispatch is asynchronous, so the pattern

    read slab -> launch encode -> write previous slab's shards -> block on parity

keeps disk and TPU busy simultaneously. Shard bytes are independent of batch
size (parity is a per-byte-column GF matmul), so output files stay
bit-identical to the reference's 256KB batching.
"""

from __future__ import annotations

import functools
import os
import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from ..ops import dispatch
from ..utils import fanout, numa, trace
from ..utils.stats import (EC_PIPELINE_BUFFER_WAIT, EC_PIPELINE_READ_QUEUE,
                           EC_PIPELINE_READ_STREAMS, EC_PIPELINE_SECONDS,
                           EC_PIPELINE_SLABS)
from . import idx as idx_mod
from . import needle_map, types
from .ec_locate import Geometry

# Per-shard slab size for the pipelined encoder. 4MB/shard => 40MB host reads
# per step for RS(10,4); divides 1GB and 1MB evenly.
DEFAULT_BATCH_SIZE = 4 * 1024 * 1024
# The reference's own buffer size, used when exact loop replication is wanted.
REFERENCE_BATCH_SIZE = 256 * 1024
# In-flight slabs between the reader/dispatcher thread and the shard writer.
# Depth N means up to N encode launches queued on the device while the writer
# drains earlier parities — the reference is depth-0 (strictly serial,
# ec_encoder.go:162-192).
DEFAULT_PIPELINE_DEPTH = 3


@dataclass
class EncodeStats:
    """Timing breakdown of one pipelined encode, for the overlap-measured
    artifacts BASELINE.md configs #2/#4 ask for."""

    bytes: int = 0
    batches: int = 0
    wall_s: float = 0.0
    read_s: float = 0.0  # reader thread: file reads + zero fill
    dispatch_s: float = 0.0  # reader thread: encode launch (sync coders: the
    #                          whole encode; async JAX dispatch: ~0)
    device_wait_s: float = 0.0  # coordinator: blocked on parity futures
    write_s: float = 0.0  # SUM across all shard-writer threads (aggregate
    #                       thread-seconds, not wall — 14 writers in parallel
    #                       can log 14s of write_s per wall second)
    started: float = field(default_factory=time.perf_counter)
    ended: float = 0.0

    @property
    def overlap_ratio(self) -> float:
        """(read + encode + device-wait + write) / wall — >1 proves phases
        ran concurrently (the reference's serial loop is exactly 1.0)."""
        if self.wall_s <= 0:
            return 0.0
        return (
            self.read_s + self.dispatch_s + self.device_wait_s + self.write_s
        ) / self.wall_s


def _writer_thread_count(n_files: int) -> int:
    """Writer parallelism, adaptive to the host. The shard files are
    independent streams and parallel writing lifts aggregate disk
    bandwidth (measured here: 153 MB/s one stream vs 457 MB/s at depth
    14) — but each thread costs scheduling overhead, so a 1-core box
    (this container) gets 2 (data/parity overlap only) while a real
    volume server gets up to one per shard file. The reference's write
    loop is strictly serial (ec_encoder.go:179-189)."""
    n = os.environ.get("SEAWEEDFS_TPU_EC_WRITERS")
    if n:
        return max(1, min(n_files, int(n)))
    return min(n_files, max(2, 2 * (os.cpu_count() or 1)))


def _read_streams(rows: int) -> int:
    """Concurrent positional reads that fill one slab: one per row, up
    to one per CPU. A one-CPU host reads a slab's rows serially on the
    reader thread, in row order. On a v5e host's 9p root, ten concurrent
    4 MiB survivor reads took 2.8 ms against 16.6 ms for one stream into
    the same buffers (PERF.md, section 6)."""
    return max(1, min(rows, os.cpu_count() or 1))


def _pread_full(fd: int, view: memoryview, offset: int) -> int:
    """Positional read into `view` until it is full or the file ends;
    returns the bytes read. A positional read may return short (9p
    does), so it loops."""
    got = 0
    while got < len(view):
        n = os.preadv(fd, [view[got:]], offset + got)
        if n == 0:
            break
        got += n
    return got


def _pread_rows(reads: list, streams: int, phases: "_Phases") -> list[int]:
    """Fill every (fd, offset, view) of one slab and return the bytes
    read into each. With one stream the reads run here, in order; else
    they are cut into `streams` runs of consecutive reads on the shared
    `ec_read` fan-out pool (one worker per CPU, shared by every
    pipeline of the process, so not pinned to a pipeline's NUMA node),
    and all of them have landed before this returns, a failed one
    included. The wait from handing the runs to the pool until the
    first of them starts is the slab's `read_queue` phase: the worker
    reads the clock, this thread closes the phase on that reading."""
    def fill(part):
        return [_pread_full(fd, view, off) for fd, off, view in part]

    if streams <= 1:
        return fill(reads)
    n = len(reads)
    parts = [reads[i * n // streams:(i + 1) * n // streams]
             for i in range(streams)]
    started: list[float] = []
    first = threading.Event()

    def run(part):
        if not started:
            started.append(time.perf_counter())
            first.set()
        return fill(part)

    pool = fanout.executor("ec_read", os.cpu_count() or 1)
    with phases.read_queue() as queued:
        futs = [pool.submit(run, part) for part in parts]
        first.wait()
        queued.until(min(started))
    return [g for part in fanout.gather(futs) for g in part]


_STATS_FIELD = {"read": "read_s", "submit": "dispatch_s",
                "device_wait": "device_wait_s", "write": "write_s"}


class _Phases:
    """The per-slab phases of one encode or rebuild. Each phase is timed
    once, on the thread where its work happens (`utils/trace.timed`), and
    that one reading is the span `ec.<op>.<phase>`, a share of
    `SeaweedFS_ec_pipeline_seconds{op, phase}` and the matching
    `EncodeStats` field. The spans are children of the span active where
    the pipeline started (`volume.ec.encode` / `volume.ec.rebuild`); the
    reader and writer threads get it as their explicit parent, and
    outside any span nothing is recorded."""

    def __init__(self, op: str, stats: EncodeStats):
        self.op = op
        self.stats = stats
        self.parent = trace.current_context()
        self._lock = threading.Lock()
        self._observe = {p: (lambda s, p=p: self._add(p, s))
                         for p in _STATS_FIELD}

    def _add(self, phase: str, seconds: float) -> None:
        EC_PIPELINE_SECONDS.inc(seconds, op=self.op, phase=phase)
        field_name = _STATS_FIELD[phase]
        with self._lock:  # writes land from several writer threads
            setattr(self.stats, field_name,
                    getattr(self.stats, field_name) + seconds)

    def timed(self, phase: str, **attrs):
        return trace.timed(f"ec.{self.op}.{phase}", parent=self.parent,
                           child_only=True, observe=self._observe[phase],
                           **attrs)

    def read(self, streams: int):
        """The `read` phase of one slab, filled by `streams` concurrent
        reads: the span's attribute `streams` and
        `SeaweedFS_ec_pipeline_read_streams{op}`."""
        EC_PIPELINE_READ_STREAMS.inc(streams, op=self.op)
        return self.timed("read", streams=streams)

    def read_queue(self):
        """Inside `read`, on the reader thread: the slab's reads queued
        on the `ec_read` pool. Its span is a child of the slab's `read`
        span; its seconds go to `SeaweedFS_ec_pipeline_read_queue_seconds
        {op}` alone, since `read` already holds them."""
        return trace.timed(f"ec.{self.op}.read_queue", child_only=True,
                           observe=self._observe_queue)

    def _observe_queue(self, seconds: float) -> None:
        EC_PIPELINE_READ_QUEUE.inc(seconds, op=self.op)

    def buffer_wait(self):
        """On the reader thread, before a slab's `read`: blocked until
        the pool hands back a recycled buffer. Its span is a child of
        the pipeline's root span; its seconds go to
        `SeaweedFS_ec_pipeline_buffer_wait_seconds{op}` alone."""
        return trace.timed(f"ec.{self.op}.buffer_wait", parent=self.parent,
                           child_only=True, observe=self._observe_buffer)

    def _observe_buffer(self, seconds: float) -> None:
        EC_PIPELINE_BUFFER_WAIT.inc(seconds, op=self.op)

    def slab(self) -> None:
        """One slab through the coordinator."""
        self.stats.batches += 1
        EC_PIPELINE_SLABS.inc(op=self.op)


class _ShardWriters:
    """Shard files fanned out over writer threads; each shard maps to
    exactly one thread, so per-shard write order is preserved while
    independent files stream in parallel. Each data-shard row of a slab
    counts down its recycled read buffer's `_Countdown` once it is on
    disk. Each row written is one `write` phase of `phases`."""

    def __init__(self, files: dict[int, object], phases: _Phases,
                 depth: int, n_threads: int | None = None,
                 numa_node: int | None = None):
        self._files = files
        self._phases = phases
        self._numa_node = numa_node
        n = n_threads or _writer_thread_count(len(files))
        self._lanes: list[queue.Queue] = [
            queue.Queue(maxsize=max(2, depth) * max(1, len(files) // n))
            for _ in range(n)
        ]
        self._qs: dict[int, queue.Queue] = {
            shard_id: self._lanes[i % n]
            for i, shard_id in enumerate(sorted(files))
        }
        self._errors: list[BaseException] = []
        self._threads = [
            threading.Thread(target=self._run, args=(lane,),
                             name=f"ec-shard-writer-{i}", daemon=True)
            for i, lane in enumerate(self._lanes)
        ]
        for t in self._threads:
            t.start()

    def _run(self, q: queue.Queue) -> None:
        # NUMA-affine writers (ISSUE 12, SWFS_EC_DISPATCH_PIN): every
        # writer pins to the SAME node as its pipeline's reader (the
        # shared numa_node draw) — the rows a writer drains were packed
        # by that reader, so splitting the pair across nodes would turn
        # each drain into remote traffic; no-op when the gate is closed
        numa.pin_thread(self._numa_node)
        while True:
            item = q.get()
            if item is None:
                return
            shard_id, arr, nbytes, release = item
            if not self._errors:  # fail fast but keep draining queues
                with self._phases.timed("write"):
                    try:
                        from ..utils import failpoint

                        if failpoint.is_armed("ec.shard.write.corrupt"):
                            # chaos hook (scrub plane): flip the first
                            # byte of a targeted shard's slab as it lands
                            # on disk — simulated shard bit rot the EC
                            # syndrome sweep must find (ctx comma-
                            # terminates the id so @shard=1, can't
                            # substring-hit shard 10)
                            raw = bytes(memoryview(arr)[:nbytes])
                            out = failpoint.corrupt(
                                "ec.shard.write.corrupt", raw,
                                ctx=f"shard={shard_id},")
                            if out is not raw:
                                arr = memoryview(out)
                        self._files[shard_id].write(
                            memoryview(arr)[:nbytes])
                    except BaseException as e:
                        self._errors.append(e)
            if release is not None:
                release()

    def put(self, shard_id: int, arr, nbytes: int, release=None) -> None:
        self._qs[shard_id].put((shard_id, arr, nbytes, release))

    def close(self) -> None:
        """Flush all queues, join threads, surface the first write error."""
        for q in self._lanes:
            q.put(None)
        for t in self._threads:
            t.join()
        if self._errors:
            raise self._errors[0]

    def abort(self) -> None:
        """Drain without raising (cleanup on another failure path)."""
        for q in self._lanes:
            try:
                q.put_nowait(None)
            except queue.Full:
                self._errors.append(RuntimeError("abort"))
                while True:
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        break
                q.put(None)
        for t in self._threads:
            t.join(timeout=5)


class _Countdown:
    """Call `cb` after `n` calls. The encode pipeline frees a recycled
    read buffer with one of k + 1: a call from each data-shard writer
    once its row is on disk, and one from the coordinator once the
    slab's parity is host bytes (the device has read the slab by then,
    which the scheduler references without a copy)."""

    __slots__ = ("_n", "_cb", "_lock")

    def __init__(self, n: int, cb):
        self._n = n
        self._cb = cb
        self._lock = threading.Lock()

    def __call__(self) -> None:
        with self._lock:
            self._n -= 1
            fire = self._n == 0
        if fire:
            self._cb()


def _pick_batch(block_size: int, requested: int) -> int:
    """Largest batch <= requested that divides block_size (the reference
    requires blockSize %% bufferSize == 0, ec_encoder.go:124)."""
    if block_size <= requested:
        return block_size
    b = requested
    while block_size % b != 0:
        b //= 2
    return max(b, 1)


def generate_ec_files(
    base_file_name: str,
    coder,
    geo: Geometry = Geometry(),
    batch_size: int = DEFAULT_BATCH_SIZE,
    pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
    sinks=None,
    pace=None,
) -> EncodeStats:
    """<base>.dat -> <base>.ec00..ecNN (WriteEcFiles / generateEcFiles /
    encodeDatFile, ec_encoder.go:56-87,194-231).

    `coder` must expose encode_parity(data[k, B] uint8) -> parity[m, B]
    (models.coder.ErasureCoder).

    Pipeline, `pipeline_depth` slabs deep, with per-shard writer fan-out:

      read streams:    the slab's k rows, concurrent positional reads
                        │ (all landed: the slab is whole)
      reader thread:   launch encode (async JAX dispatch)             ┐
                                                               bounded queue
      coordinator:     route data rows to writers -> block on parity  ┘
      14 shard writers: one thread per output file (independent streams;
                        queue-depth-14 writing measures ~3x one stream)

    The read streams are `_read_streams(rows)` positional reads on the
    process-wide `ec_read` fan-out pool (one worker per CPU, shared by
    every pipeline); a one-CPU host reads the rows on the reader thread.
    A slab is whole before it is submitted or handed to `sinks`. A
    recycled buffer pool caps host memory at ~(depth+2) slabs; a slab's
    buffer is recycled only after every data-shard writer flushed its row
    and its parity is host bytes (countdown), so the slab is submitted
    as a C-contiguous view of its buffer, without a copy. The reader's
    wait for a buffer is `ec.encode.buffer_wait`. Multiple volumes
    encoding concurrently (BASELINE config #4) each run their own
    pipeline; their encode launches interleave on the shared device
    queue, so host I/O of one volume overlaps device math of another.

    `sinks` (ISSUE 6, storage/ec_stream.py EcStreamSinkSet) is the
    pluggable shard-sink hook: an object whose
    put(shard_id, shard_offset, row, nbytes) receives every slab row the
    moment it exists — data rows before the parity dispatch resolves,
    parity rows right after — so network transfer to the shards'
    destination servers overlaps the encode itself. Sinks copy the bytes
    synchronously (the pipeline recycles its buffers); local shard files
    are written regardless (they are the resume source and keep bytes
    bit-identical to the generate-then-copy path by construction).

    Each slab's phases (read, submit, device_wait, write) are timed once
    into the spans `ec.encode.<phase>`, `SeaweedFS_ec_pipeline_*` and the
    returned EncodeStats (`_Phases`).

    `pace` (ISSUE 8, qos/priority.py BackgroundGovernor.pacer) is called
    with each slab's data-byte count before the slab's rows are routed
    to writers/sinks; it may block (waiting on cluster tokens) or raise
    (QosUnavailable mid-job) — the pipeline aborts cleanly either way.
    """
    k, m = geo.data_shards, geo.parity_shards
    dat_path = base_file_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    stats = EncodeStats()
    phases = _Phases("encode", stats)
    depth = max(1, pipeline_depth)

    outs = [open(geo.shard_file_name(base_file_name, i), "wb") for i in range(k + m)]
    # preallocate: every shard file has the same known final size, so the
    # 14 parallel write streams get contiguous extents instead of racing
    # each other for allocations
    shard_size = geo.shard_size(dat_size)
    fallocate = getattr(os, "posix_fallocate", None)  # absent off-Linux
    if shard_size and fallocate:
        for f2 in outs:
            try:
                fallocate(f2.fileno(), 0, shard_size)
            except OSError:
                continue  # best-effort PER FILE (one ENOSPC/EOPNOTSUPP
                #           must not strip preallocation from the rest)
    free_q: queue.Queue = queue.Queue()
    max_batch = min(batch_size, max(geo.large_block, geo.small_block))
    for _ in range(depth + 2):
        free_q.put(np.empty((k, max_batch), dtype=np.uint8))
    work_q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    # EC dispatch scheduler (ops/dispatch.py): slabs from THIS pipeline and
    # any other volume encoding concurrently through the same coder share
    # stacked [V, k, B] device dispatches. The futures it returns answer
    # np.asarray just like the lazy device array from a direct call, and
    # shard bytes stay identical (zero-padded ragged columns slice away).
    # No snapshot at submit: the slab's buffer is not recycled before its
    # parity is host bytes (the coordinator's release of the countdown).
    sched = dispatch.maybe_scheduler(coder)
    encode = (coder.encode_parity if sched is None
              else functools.partial(sched.encode_parity, copy=False))
    # one node per PIPELINE (ISSUE 12): reader and writers share it, so
    # the recycled read buffers stay node-local end to end; separate
    # concurrent pipelines round-robin across nodes via their own draws
    pipe_node = numa.next_node()

    def reader() -> None:
        numa.pin_thread(pipe_node)  # reads + encode launches node-local
        try:
            with open(dat_path, "rb") as f:
                fd = f.fileno()
                processed = 0
                for block_size in _row_schedule(geo, dat_size):
                    batch = _pick_batch(block_size, batch_size)
                    for b in range(0, block_size, batch):
                        if stop.is_set():
                            return
                        with phases.buffer_wait():
                            buf = free_q.get()
                        if stop.is_set() or buf.shape[1] < batch:
                            return
                        # the slab's k rows packed at the buffer's start:
                        # one dense [k, batch] block for the transfer
                        data = buf.reshape(-1)[:k * batch].reshape(k, batch)
                        # row i of the slab is .dat bytes from `start`;
                        # ReadAt with zero fill past EOF
                        # (ec_encoder.go:165-177)
                        reads = []
                        for i in range(k):
                            start = processed + block_size * i + b
                            if start < dat_size:
                                reads.append((fd, start, memoryview(
                                    data[i])[:min(batch, dat_size - start)]))
                        streams = _read_streams(len(reads))
                        with phases.read(streams):
                            got = _pread_rows(reads, streams, phases)
                            for i in range(k):
                                n = got[i] if i < len(got) else 0
                                if n < batch:
                                    data[i, n:] = 0
                        with phases.timed("submit"):
                            parity_fut = encode(data)
                        work_q.put((buf, data, parity_fut, batch))
                    processed += block_size * k
            work_q.put(None)
        except BaseException as e:  # surface in the coordinator/caller
            work_q.put(e)

    writers = _ShardWriters(dict(enumerate(outs)), phases, depth,
                            numa_node=pipe_node)
    t = threading.Thread(target=reader, name="ec-encode-reader", daemon=True)
    t.start()
    ok = False
    shard_off = 0  # every shard advances by the same nbytes per slab
    try:
        while True:
            item = work_q.get()
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            buf, data, parity_fut, nbytes = item
            if pace is not None:
                # cluster-budget draw, one slab at a time: may block on
                # higher-priority demand (the reader works ahead only to
                # the bounded queue depth); a raise aborts the pipeline
                # through the normal failure path below
                pace(k * nbytes)
            release = _Countdown(k + 1, lambda b=buf: free_q.put(b))
            if sinks is not None:
                # data rows stream BEFORE the writers get the buffer:
                # sinks copy synchronously here, since the countdown
                # recycles the buffer under a concurrent reader refill
                # once the writers and this thread have released it
                for i in range(k):
                    sinks.put(i, shard_off, data[i], nbytes)
            for i in range(k):
                writers.put(i, data[i], nbytes, release)
            with phases.timed("device_wait"):
                parity = np.asarray(parity_fut)  # blocks until device done
            release()  # the device has read the slab
            for j in range(m):
                # parity rows are views of one fresh array; numpy refcounts
                # keep it alive until the last writer drops its view
                writers.put(k + j, parity[j], nbytes)
                if sinks is not None:
                    sinks.put(k + j, shard_off, parity[j], nbytes)
            shard_off += nbytes
            phases.slab()
            stats.bytes += k * nbytes
        writers.close()
        ok = True
    finally:
        stop.set()
        if not ok:
            writers.abort()
        # unblock a reader stuck on free_q.get(), then drain
        free_q.put(np.empty((k, 0), dtype=np.uint8))
        while t.is_alive():
            try:
                work_q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=0.05)
        for f2 in outs:
            f2.close()
    stats.ended = time.perf_counter()
    stats.wall_s = stats.ended - stats.started
    return stats


def _row_schedule(geo: Geometry, dat_size: int):
    """Yield the per-row block sizes encodeDatFile walks (ec_encoder.go:214-229):
    strict-> large rows while remaining > large_row, then small rows while > 0."""
    n_large, n_small = geo.row_counts(dat_size)
    for _ in range(n_large):
        yield geo.large_block
    for _ in range(n_small):
        yield geo.small_block


def write_ec_files(
    base_file_name: str, coder, geo: Geometry = Geometry(), sinks=None,
    pace=None,
) -> EncodeStats:
    """WriteEcFiles equivalent (ec_encoder.go:56-59)."""
    return generate_ec_files(base_file_name, coder, geo, sinks=sinks,
                             pace=pace)


def write_ecx_stride_marker(base_file_name: str) -> None:
    """Sync the per-index `.ecx.lrg` marker to the active offset width.

    EC index files carry their OWN marker, distinct from the volume's
    `.lrg`: shards travel between servers independently of any .dat
    volume sharing the base name, so one shared marker could describe
    at most one of the two artifact families correctly."""
    if types.large_disk():
        with open(base_file_name + ".ecx.lrg", "wb"):
            pass
    else:
        try:
            os.remove(base_file_name + ".ecx.lrg")
        except FileNotFoundError:
            pass


def check_ecx_stride(base_file_name: str) -> None:
    """Refuse to parse a .ecx across an offset-width mismatch — the
    size-modulus heuristic alone misses entry counts that are multiples
    of both strides. Every .ecx-consuming path (EcVolume open, ec-decode)
    must call this before reading entries."""
    has_marker = os.path.exists(base_file_name + ".ecx.lrg")
    if has_marker != types.large_disk():
        raise IOError(
            f"ec volume {base_file_name}: index stride mismatch — .ecx "
            f"was written with {'5' if has_marker else '4'}-byte offsets "
            f"but the process is in "
            f"{'large-disk (5-byte)' if types.large_disk() else '4-byte'} "
            f"mode; restart with the matching -largeDisk setting"
        )


def write_sorted_file_from_idx(base_file_name: str, ext: str = ".ecx") -> None:
    needle_map.write_sorted_file_from_idx(base_file_name, ext)
    # .ecx entries use the active offset width: stamp the marker so the
    # .ecx-consuming guards recognize it
    write_ecx_stride_marker(base_file_name)


def rebuild_ec_files(
    base_file_name: str,
    coder,
    geo: Geometry = Geometry(),
    batch_size: int = DEFAULT_BATCH_SIZE,
    pace=None,
    want: list[int] | None = None,
    stats: dict | None = None,
) -> list[int]:
    """Regenerate missing .ecNN files from the survivors
    (RebuildEcFiles / generateMissingEcFiles / rebuildEcFiles,
    ec_encoder.go:61-63,89-118,233-287). Returns the rebuilt shard ids.
    `pace`, as in generate_ec_files, draws each slab's survivor-read
    bytes from the cluster background budget before the slab is
    written.

    ISSUE 11: the rebuild reads only the geometry's MINIMAL-READ repair
    plan (models/geometry.py) — a single lost shard inside an
    lrc_10_2_2 local group reads its 5 group peers instead of 10
    survivors (RS reads exactly its first-k decode set, never the
    surplus). `want` restricts the rebuild to those shard ids (the
    genuinely-missing set cluster-wide — locally-absent shards that
    exist on peers need no rebuild here); `stats`, when given, receives
    survivor_bytes_read / survivor_shards / geometry / slabs. The slab
    phases are timed as in generate_ec_files, under `ec.rebuild.*`.

    Pipeline, DEFAULT_PIPELINE_DEPTH slabs deep:

      read streams:    P survivor rows at one offset, concurrent
                        │ positional reads (all landed: the slab is whole)
      reader thread:   submit the reconstruct                         ┐
                                                               bounded queue
      coordinator:     block on the rebuilt rows -> recycle the       ┘
                       survivor buffer -> route rows to writers
      writers:         one thread per missing shard file

    Survivor buffers come from a pool of DEFAULT_PIPELINE_DEPTH + 2."""
    total = geo.total_shards
    have = [os.path.exists(geo.shard_file_name(base_file_name, i)) for i in range(total)]
    missing = [i for i in range(total) if not have[i]]
    if want is not None:
        missing = [i for i in missing if i in set(want)]
    if not missing:
        return []
    present = [i for i in range(total) if have[i]]

    from ..models.geometry import UnsolvableError
    from ..utils.stats import EC_REPAIR_BYTES, EC_REPAIR_PLANS

    geom = geo.code_geometry()
    try:
        plan = geom.repair_plan(tuple(missing), tuple(present))
    except (UnsolvableError, ValueError):
        raise ValueError(
            f"too many shards missing: have {len(present)} "
            f"({geo.code_name}), cannot rebuild {missing}"
        )
    reads = list(plan.reads)
    ins = {i: open(geo.shard_file_name(base_file_name, i), "rb") for i in reads}
    outs = {i: open(geo.shard_file_name(base_file_name, i), "wb") for i in missing}
    shard_size = os.path.getsize(geo.shard_file_name(base_file_name, reads[0]))
    fallocate = getattr(os, "posix_fallocate", None)  # absent off-Linux
    if shard_size and fallocate:
        for f in outs.values():
            try:
                fallocate(f.fileno(), 0, shard_size)
            except OSError:
                continue  # best-effort per file, as in generate_ec_files
    # Same pipeline shape as the encoder: a reader thread dispatches
    # reconstructs asynchronously; the coordinator drains an N-deep queue
    # and fans rebuilt rows out to one writer thread per missing shard.
    work_q: queue.Queue = queue.Queue(maxsize=DEFAULT_PIPELINE_DEPTH)
    stop = threading.Event()

    use_stacked = hasattr(coder, "reconstruct_stacked")
    if not use_stacked and set(reads) != set(present):
        # exotic coder without the want= stacked form: no minimal-read —
        # fall back to the full survivor set and the dict path
        for i in present:
            if i not in ins:
                ins[i] = open(geo.shard_file_name(base_file_name, i), "rb")
        reads = list(present)
    if stats is not None:
        # recorded AFTER any fallback widening, so shard/byte accounting
        # always describes the survivor set actually read
        stats["geometry"] = geo.code_name
        stats["survivor_shards"] = len(reads)
        stats.setdefault("survivor_bytes_read", 0)
    reads_tuple = tuple(reads)
    want_tuple = tuple(missing)
    # share stacked reconstruct dispatches with any concurrent rebuild of
    # the same survivor set (and keep the pipeline depth working ahead:
    # futures resolve in the coordinator, not the reader)
    sched = dispatch.maybe_scheduler(coder) if use_stacked else None

    pipe_node = numa.next_node()  # shared by reader + writers (ISSUE 12)
    phases = _Phases("rebuild", EncodeStats())
    # survivors land in ONE contiguous [P, batch] buffer, so the stacked
    # reconstruct runs a single column-permuted matmul with no
    # device-side re-stack. The buffers are recycled as in
    # generate_ec_files: a fresh 40 MiB buffer a slab spent most of its
    # read in first-touch page faults. A buffer goes back to the pool
    # only once its slab's rows are host bytes (end of device_wait): the
    # scheduler references it without a copy until then.
    cols = min(batch_size, shard_size)
    free_q: queue.Queue = queue.Queue()
    for _ in range(DEFAULT_PIPELINE_DEPTH + 2):
        free_q.put(np.empty((len(reads), cols), dtype=np.uint8))
    fds = [ins[i].fileno() for i in reads]
    streams = _read_streams(len(reads))

    def reader() -> None:
        numa.pin_thread(pipe_node)  # survivor reads stay node-local
        try:
            # every survivor is as long as the first: the loop then stops
            # at that length and times no read past the last slab
            for fd in fds:
                size = os.fstat(fd).st_size
                if size != shard_size:
                    raise IOError(f"ec shard size mismatch: expected "
                                  f"{shard_size} got {size}")
            offset = 0
            while offset < shard_size and not stop.is_set():
                with phases.buffer_wait():
                    stacked = free_q.get()
                if stacked is None or stop.is_set():
                    return
                n = min(cols, shard_size - offset)
                with phases.read(streams):
                    got = _pread_rows([(fd, offset, memoryview(row)[:n])
                                       for fd, row in zip(fds, stacked)],
                                      streams, phases)
                for g in got:
                    if g != n:
                        raise IOError(f"ec shard size mismatch: expected "
                                      f"{n} got {g}")
                with phases.timed("submit"):
                    if sched is not None:
                        # the buffer is not recycled before the future
                        # resolves: the slab references it without a copy
                        out = sched.reconstruct_stacked(
                            reads_tuple, stacked[:, :n], want=want_tuple)
                    elif use_stacked:
                        mids, rows = coder.reconstruct_stacked(
                            reads_tuple, stacked[:, :n], want=want_tuple)
                        out = dict(zip(mids, rows))
                    else:
                        out = coder.reconstruct(
                            {i: stacked[j, :n] for j, i in enumerate(reads)})
                work_q.put((stacked, out))
                offset += n
            work_q.put(None)
        except BaseException as e:
            work_q.put(e)

    writers = _ShardWriters(outs, phases, DEFAULT_PIPELINE_DEPTH,
                            numa_node=pipe_node)
    t = threading.Thread(target=reader, name="ec-rebuild-reader", daemon=True)
    t.start()
    ok = False
    try:
        while True:
            item = work_q.get()
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            stacked, rebuilt = item
            with phases.timed("device_wait"):
                if isinstance(rebuilt, dispatch.EcFuture):
                    mids, rows = rebuilt.result()
                    rebuilt = dict(zip(mids, rows))
                host = {i: np.ascontiguousarray(
                    np.asarray(rebuilt[i], dtype=np.uint8)) for i in missing}
            free_q.put(stacked)
            slab_bytes = len(reads) * len(host[missing[0]])
            if pace is not None:
                # repair-class budget draw: survivors read this slab —
                # the minimal-read plan draws proportionally less
                pace(slab_bytes)
            EC_REPAIR_BYTES.inc(slab_bytes, geometry=geo.code_name,
                                kind="rebuild", source="local")
            if stats is not None:
                stats["survivor_bytes_read"] += slab_bytes
            for i in missing:
                writers.put(i, host[i], len(host[i]))
            phases.slab()
        writers.close()
        ok = True
        EC_REPAIR_PLANS.inc(geometry=geo.code_name, kind="rebuild")
        if stats is not None:
            stats["slabs"] = phases.stats.batches
    finally:
        stop.set()
        if not ok:
            writers.abort()
        free_q.put(None)  # unblock a reader stuck on free_q.get()
        while t.is_alive():
            try:
                work_q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=0.05)
        for f in ins.values():
            f.close()
        for f in outs.values():
            f.close()
    return missing


# -- Decode back to a plain volume (ec_decoder.go) ---------------------------


def find_dat_file_size(
    base_file_name: str,
    version: int = types.CURRENT_VERSION,
) -> int:
    """True .dat length = max(offset + actual_size) over live .ecx entries
    (FindDatFileSize, ec_decoder.go:48-70)."""
    check_ecx_stride(base_file_name)
    dat_size = 0
    ids, offs, sizes = idx_mod.read_index_file(base_file_name + ".ecx")
    for i in range(len(ids)):
        size = int(sizes[i])
        if types.size_is_deleted(size):
            continue
        entry_stop = types.stored_to_actual_offset(int(offs[i])) + types.actual_size(
            size, version
        )
        dat_size = max(dat_size, entry_stop)
    return dat_size


def write_dat_file(
    base_file_name: str,
    dat_file_size: int,
    geo: Geometry = Geometry(),
    shard_file_names: list[str] | None = None,
) -> None:
    """Re-interleave data shards .ec00..ec<k-1> into <base>.dat
    (WriteDatFile, ec_decoder.go:153-201). Note the reference's large-row
    loop here is `>=` where the encoder's is strict `>` — replicated as-is,
    quirk included."""
    k = geo.data_shards
    names = shard_file_names or [geo.shard_file_name(base_file_name, i) for i in range(k)]
    types.write_stride_marker(base_file_name)
    ins = [open(names[i], "rb") for i in range(k)]
    try:
        with open(base_file_name + ".dat", "wb") as out:
            remaining = dat_file_size
            while remaining >= k * geo.large_block:
                for i in range(k):
                    chunk = ins[i].read(geo.large_block)
                    if len(chunk) != geo.large_block:
                        raise IOError(f"short large block from {names[i]}")
                    out.write(chunk)
                    remaining -= geo.large_block
            while remaining > 0:
                for i in range(k):
                    take = min(remaining, geo.small_block)
                    if take <= 0:
                        break
                    chunk = ins[i].read(take)
                    if len(chunk) != take:
                        raise IOError(f"short small block from {names[i]}")
                    out.write(chunk)
                    remaining -= take
    finally:
        for f in ins:
            f.close()


def write_idx_file_from_ec_index(base_file_name: str) -> None:
    """Reconstruct <base>.idx from .ecx + .ecj tombstones
    (WriteIdxFileFromEcIndex, ec_decoder.go:18-43): copy .ecx, then append a
    tombstone entry per journaled deletion."""
    check_ecx_stride(base_file_name)  # .idx inherits the .ecx entry bytes
    ecx = base_file_name + ".ecx"
    with open(ecx, "rb") as f:
        payload = f.read()
    extra = b""
    ecj = base_file_name + ".ecj"
    if os.path.exists(ecj):
        with open(ecj, "rb") as f:
            j = f.read()
        for i in range(0, len(j) - 7, types.NEEDLE_ID_SIZE):
            nid = int.from_bytes(j[i : i + 8], "big")
            extra += types.pack_needle_map_entry(nid, 0, types.TOMBSTONE_FILE_SIZE)
    with open(base_file_name + ".idx", "wb") as f:
        f.write(payload + extra)
