"""EC dispatch scheduler: amortize device round-trips across the EC plane.

The encode/rebuild pipelines (storage/ec_files.py) and the degraded-read
serving path (server/volume.py, storage/ec_volume.py) all end in the same
shape of work: a GF matmul over a [rows, B] slab, one device round-trip
per slab. Each round-trip costs a fixed dispatch latency, so many small
dispatches spend their time on launch overhead. Parity and
reconstruction are per-byte-column GF matmuls — slabs from DIFFERENT
volumes or requests can share one dispatch by laying their columns side
by side, bit-identically.

This module is that sharing point:

  * slabs submitted by concurrent pipelines land in per-kind *lanes*
    (encode slabs share one lane per geometry; reconstruct slabs share a
    lane per survivor set — same fused matrix, so same dispatch);
  * a lane flushes as ONE stacked dispatch (`encode_parity_stacked` /
    `reconstruct_stacked`) when its flush window expires
    (SWFS_EC_DISPATCH_WINDOW_MS, default 2ms), when it reaches
    SWFS_EC_DISPATCH_MAX_SLABS, or the moment a consumer blocks on one of
    its futures (demand flush — a pipeline draining its queue never pays
    the window as latency);
  * submission order is preserved per lane, so each volume's slabs
    dispatch FIFO (a volume's pipeline submits from one thread).

Scheduling/fusion of coding ops — not the GF math — dominates real EC
throughput (arxiv 2108.02692); pipelining erasure coding across
concurrent streams is the archival-throughput lever (RapidRAID,
arxiv 1207.6744). The scheduler applies both without changing a single
output byte: tests pin .ec00-.ec13 bit-identity with the scheduler on
and off.

The HOST side of a flush is its own optimization target (ISSUE 12): once
the GF arithmetic is fast, software-EC throughput lives in memory
traffic, not ALU work (arXiv:2108.02692). A flush therefore packs its
slabs into a recycled page-aligned `StackArena` buffer instead of
allocating a fresh zero-filled stack per batch: encode/reconstruct
batches pack COLUMN-COMPACTLY (`[rows, sum(widths)]`, zero-fill fully
elided — every byte is payload) and mesh V-axis batches pack `[V, rows,
B]` with only ragged tails memset. Arena buffers are recycled only after
the dispatch has provably consumed the bytes (synchronous backends:
immediately; async jax dispatches: once the output `is_ready()`, which
also covers the CPU client's zero-copy aliasing of page-aligned host
buffers) — never while an `EcFuture` could still read them. The flusher
thread can optionally be NUMA-pinned (`SWFS_EC_DISPATCH_PIN`,
utils/numa.py).

Also here: `ReconstructIntervalCache`, the bounded LRU of reconstructed
shard blocks serving repeated degraded reads of a hot lost shard
(server/volume.py keys it by (vid, shard_id, block) and invalidates on
shard mount/unmount/delete).
"""

from __future__ import annotations

import atexit
import itertools
import os
import threading
import time
import weakref
from collections import OrderedDict

import numpy as np

from . import rs_sched
from ..utils import locks, numa, trace
from ..utils.stats import (
    EC_DISPATCH_ARENA_INUSE,
    EC_DISPATCH_ARENA_OPS,
    EC_DISPATCH_ARENA_POOLED,
    EC_DISPATCH_BATCHES,
    EC_DISPATCH_LOCK_WAIT,
    EC_DISPATCH_OVERLAPPED,
    EC_DISPATCH_SLABS,
    EC_DISPATCH_STACK_BYTES,
    EC_DISPATCH_STACK_SLABS,
    EC_DISPATCH_SUBMIT_COPIED,
    EC_DISPATCH_WINDOW_WAIT,
    EC_DISPATCH_ZEROFILL_ELIDED,
    EC_RECON_CACHE_COUNTER,
)

DEFAULT_WINDOW_MS = 2.0
# `reason` labels of EC_DISPATCH_BATCHES under which a lane ran on a host
# coder instead of the device (see EcDispatchScheduler._lane_reason)
HOST_REASONS = frozenset({"cpu_env", "cpu_explicit"})
DEFAULT_MAX_SLABS = 32
# survivor-set -> chip assignments kept per scheduler (LRU): each set's
# fused decode matrix lives on its assigned chip (ops/rs_jax._op_on_device)
DEFAULT_REC_SETS = 128
# flusher thread exits after this long with no pending work (a fresh
# submit restarts it) — idle schedulers self-clean instead of leaking a
# thread per coder across tests
_IDLE_EXIT_S = 1.0
# witness rank of chip 0's submission lock; chip i's is one more per
# index, so every chip lock ranks above dispatch.mu (100) and below
# dispatch.lane_cv (200), and taking them in index order is legal
_CHIP_RANK0 = 101


def enabled() -> bool:
    """SWFS_EC_DISPATCH gates the whole plane (default on)."""
    return os.environ.get("SWFS_EC_DISPATCH", "1").lower() not in (
        "0", "false", "off")


def vshard_enabled() -> bool:
    """SWFS_EC_DISPATCH_VSHARD gates the per-chip (V-axis) lanes on
    mesh-backed coders (default on; single-device coders ignore it)."""
    return os.environ.get("SWFS_EC_DISPATCH_VSHARD", "1").lower() not in (
        "0", "false", "off")


def window_s() -> float:
    return float(os.environ.get("SWFS_EC_DISPATCH_WINDOW_MS",
                                str(DEFAULT_WINDOW_MS))) / 1000.0


def arena_enabled() -> bool:
    """SWFS_EC_DISPATCH_ARENA gates the host memory plane (ISSUE 12):
    recycled flush buffers instead of a fresh zero-filled stack per
    batch (default on; 0 restores the allocate-per-flush path)."""
    return os.environ.get("SWFS_EC_DISPATCH_ARENA", "1").lower() not in (
        "0", "false", "off")


# -- stack arena (ISSUE 12): the host memory plane ---------------------------

_PAGE = 4096
DEFAULT_ARENA_POOL_MB = 256
DEFAULT_ARENA_POOL_BUFS = 8


def _aligned_empty(nbytes: int) -> np.ndarray:
    """Page-aligned uint8 buffer of `nbytes` (a view into a slightly
    larger allocation; the view keeps the backing array alive). Page
    alignment matters twice: jax's CPU client zero-copies page-aligned
    host buffers into device arrays (no memcpy on commit), and the
    native plane's ctypes kernels read the buffer in aligned streams."""
    raw = np.empty(nbytes + _PAGE, dtype=np.uint8)
    off = (-raw.ctypes.data) % _PAGE
    return raw[off:off + nbytes]


def _consumed(out_ref) -> bool:
    """True iff the dispatch that read an arena buffer has provably
    consumed its bytes. Synchronous backends (rs_cpu / rs_native) return
    realized numpy arrays — consumed by construction. Async jax arrays
    expose is_ready(): once the FINAL output of a dispatch is ready,
    every producing computation (including the host->device transfer or
    zero-copy read of the input) has executed, so the input buffer is
    free. Anything unprobeable is treated as never-consumed (the arena
    then drops the buffer rather than risk recycling live bytes)."""
    if out_ref is None or isinstance(out_ref, np.ndarray):
        return True
    fn = getattr(out_ref, "is_ready", None)
    if fn is None:
        return not hasattr(out_ref, "block_until_ready")  # non-jax: sync
    try:
        return bool(fn())
    # lint: allow-broad-except(a deleted/donated device buffer raising
    # from is_ready() IS the proof its bytes were consumed)
    except Exception:  # noqa: BLE001
        return True


class _ArenaBuf:
    __slots__ = ("flat", "cap")

    def __init__(self, cap: int):
        self.flat = _aligned_empty(cap)
        self.cap = cap


class StackArena:
    """Bounded pool of reusable page-aligned host buffers for stacked
    flushes — the allocation/memset/copy diet of ISSUE 12.

    A flush checks a buffer out (`get`), packs its slabs into a view of
    it, dispatches, and hands the buffer back with the dispatch's output
    handle (`release`). The buffer returns to the free pool ONLY once
    that output proves the bytes were consumed (`_consumed`): numpy
    outputs immediately, lazy jax outputs when `is_ready()` — never
    while an in-flight async dispatch (or a zero-copy-committed device
    array) could still read the host bytes. Buffers whose dispatch never
    proves consumption are dropped, not recycled: bit-identity beats a
    pool hit, always.

    Capacities are rounded to power-of-two pages so steady-state lanes
    (same shape flush after flush) hit the same bucket every time; the
    pool is bounded by buffer count and total bytes (lane-cap sized:
    SWFS_EC_DISPATCH_ARENA_MB / _BUFS)."""

    def __init__(self, max_bufs: int | None = None,
                 max_bytes: int | None = None):
        if max_bufs is None:
            max_bufs = int(os.environ.get("SWFS_EC_DISPATCH_ARENA_BUFS",
                                          str(DEFAULT_ARENA_POOL_BUFS)))
        if max_bytes is None:
            max_bytes = int(float(os.environ.get(
                "SWFS_EC_DISPATCH_ARENA_MB",
                str(DEFAULT_ARENA_POOL_MB))) * 1024 * 1024)
        self.max_bufs = max(1, max_bufs)
        self.max_bytes = max(_PAGE, max_bytes)
        self._pool: dict[int, list[_ArenaBuf]] = {}
        self._pooled_bytes = 0
        self._inuse_bytes = 0
        self._quarantine: list[tuple[_ArenaBuf, object]] = []
        self._largest = 0
        # witnessed leaf lock (ISSUE 15): held briefly for pool
        # bookkeeping, ranked after every dispatch-plane lock
        self._mu = locks.wlock("dispatch.arena", rank=800)

    @staticmethod
    def _bucket(nbytes: int) -> int:
        cap = _PAGE
        while cap < nbytes:
            cap *= 2
        return cap

    def _sweep_locked(self) -> None:
        """Move quarantined buffers whose dispatch completed back to the
        pool (opportunistic — called from get/release, never blocks).
        The quarantine itself is bounded: a backend whose outputs never
        prove consumption sheds its oldest buffers to the GC (counted
        as drops) instead of accumulating them forever."""
        still = []
        for buf, out_ref in self._quarantine:
            if _consumed(out_ref):
                self._pool_locked(buf)
            else:
                still.append((buf, out_ref))
        while len(still) > max(8, 2 * self.max_bufs):
            buf, _ = still.pop(0)
            self._inuse_bytes -= buf.cap
            EC_DISPATCH_ARENA_INUSE.set(self._inuse_bytes)
            EC_DISPATCH_ARENA_OPS.inc(result="drop")
        self._quarantine = still

    def _pool_locked(self, buf: _ArenaBuf) -> None:
        self._inuse_bytes -= buf.cap
        bucket = self._pool.setdefault(buf.cap, [])
        n_pooled = sum(len(v) for v in self._pool.values())
        if (n_pooled >= self.max_bufs
                or self._pooled_bytes + buf.cap > self.max_bytes):
            EC_DISPATCH_ARENA_OPS.inc(result="drop")
        else:
            bucket.append(buf)
            self._pooled_bytes += buf.cap
            EC_DISPATCH_ARENA_OPS.inc(result="recycle")
        EC_DISPATCH_ARENA_INUSE.set(self._inuse_bytes)
        EC_DISPATCH_ARENA_POOLED.set(self._pooled_bytes)

    def get(self, nbytes: int) -> _ArenaBuf:
        """Smallest pooled buffer with capacity >= nbytes, else a fresh
        page-aligned allocation (miss; resize when the request outgrew
        every capacity this arena has ever served)."""
        want = self._bucket(max(1, nbytes))
        with self._mu:
            self._sweep_locked()
            for cap in sorted(self._pool):
                if cap >= want and self._pool[cap]:
                    buf = self._pool[cap].pop()
                    self._pooled_bytes -= cap
                    self._inuse_bytes += cap
                    EC_DISPATCH_ARENA_OPS.inc(result="hit")
                    EC_DISPATCH_ARENA_INUSE.set(self._inuse_bytes)
                    EC_DISPATCH_ARENA_POOLED.set(self._pooled_bytes)
                    return buf
            grew = want > self._largest
            self._largest = max(self._largest, want)
            self._inuse_bytes += want
            EC_DISPATCH_ARENA_INUSE.set(self._inuse_bytes)
        EC_DISPATCH_ARENA_OPS.inc(result="resize" if grew else "miss")
        return _ArenaBuf(want)

    def release(self, buf: _ArenaBuf, out_ref) -> None:
        """Hand a checked-out buffer back, tied to the dispatch output
        that consumed it. Recycles now when consumption is proven,
        quarantines otherwise (re-checked on later get/release)."""
        with self._mu:
            if _consumed(out_ref):
                self._pool_locked(buf)
            else:
                self._quarantine.append((buf, out_ref))
            self._sweep_locked()

    def drop(self, buf: _ArenaBuf) -> None:
        """Abandon a checked-out buffer (a dispatch that raised may have
        half-submitted async work; recycling would risk live bytes)."""
        with self._mu:
            self._inuse_bytes -= buf.cap
            EC_DISPATCH_ARENA_INUSE.set(self._inuse_bytes)
        EC_DISPATCH_ARENA_OPS.inc(result="drop")

    def stats(self) -> dict:
        with self._mu:
            return {
                "pooled": sum(len(v) for v in self._pool.values()),
                "pooledBytes": self._pooled_bytes,
                "inUseBytes": self._inuse_bytes,
                "quarantined": len(self._quarantine),
            }

    def close(self) -> None:
        """Drop everything (quarantined buffers are abandoned to the GC
        — their dispatches keep them alive exactly as long as needed)."""
        with self._mu:
            dropped = sum(len(v) for v in self._pool.values()) \
                + len(self._quarantine)
            self._pool.clear()
            self._quarantine.clear()
            self._pooled_bytes = 0
            self._inuse_bytes = 0
            EC_DISPATCH_ARENA_INUSE.set(0)
            EC_DISPATCH_ARENA_POOLED.set(0)
        if dropped:
            EC_DISPATCH_ARENA_OPS.inc(dropped, result="drop")


class EcFuture:
    """Result handle for a submitted slab. `np.asarray(fut)` works as a
    drop-in for the lazy device array the direct coder call returns.

    After resolution the future carries the dispatch attribution the
    tracing plane surfaces (ISSUE 7): how long the slab queued in its
    lane, how many slabs shared its stacked dispatch, and which chip ran
    it. Stamped BEFORE the result is set so a woken consumer never reads
    half-stamped attribution. The batch's own time is the span
    `ec.dispatch.<lane>` around its pack and coder call."""

    __slots__ = ("_event", "_value", "_error", "_sched", "_key",
                 "queue_wait_s", "batch_slabs", "chip")

    def __init__(self, sched: "EcDispatchScheduler", key: tuple):
        self._event = threading.Event()
        self._value = None
        self._error = None
        self._sched = sched
        self._key = key
        self.queue_wait_s = None
        self.batch_slabs = None
        self.chip = None

    def _set(self, value) -> None:
        self._value = value
        self._event.set()

    def _set_error(self, exc: BaseException) -> None:
        self._error = exc
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        if not self._event.is_set():
            if self._key[0] == "rec":
                # serving-side micro-batch: a degraded read already paid
                # a k-survivor fetch, so give the window a beat to
                # coalesce the other concurrent readers before forcing
                self._event.wait(self._sched.window)
            # demand flush: a STILL-blocked consumer means the window
            # has nothing left to buy — dispatch the lane NOW, on this
            # thread, batching whatever accumulated behind us. Never
            # flush once resolved: that would steal the lane's fresh
            # arrivals mid-window and fragment their batches.
            if not self._event.is_set():
                self._sched._demand_flush(self._key)
            if not self._event.wait(timeout):
                raise TimeoutError("ec dispatch result timed out")
        if self._error is not None:
            raise self._error
        return self._value

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self.result())
        if dtype is not None and out.dtype != dtype:
            return out.astype(dtype)
        return out


class _Slab:
    __slots__ = ("data", "width", "fut", "t", "ctx")

    def __init__(self, data: np.ndarray, fut: EcFuture):
        self.data = data
        self.width = data.shape[-1]
        self.fut = fut
        self.t = time.perf_counter()
        # the submitter's span: parent of the batch span, whichever
        # thread ends up flushing the lane
        self.ctx = trace.current_context()


_schedulers: "weakref.WeakSet[EcDispatchScheduler]" = weakref.WeakSet()
_attach_lock = locks.wlock("dispatch.attach")


def scheduler_for(coder) -> "EcDispatchScheduler":
    """The per-coder shared scheduler (one per store coder — every EC
    volume and pipeline on a server shares it, which is exactly the
    cross-volume amortization). Lives on the coder object itself so its
    lifetime tracks the coder's."""
    sched = getattr(coder, "_ec_dispatch_sched", None)
    if sched is None or sched.closed:
        with _attach_lock:
            sched = getattr(coder, "_ec_dispatch_sched", None)
            if sched is None or sched.closed:
                sched = EcDispatchScheduler(coder)
                coder._ec_dispatch_sched = sched
    return sched


def maybe_scheduler(coder):
    """scheduler_for(coder) when the dispatch plane is enabled, else None
    (callers fall back to direct per-slab coder calls)."""
    return scheduler_for(coder) if enabled() else None


def shutdown_all() -> None:
    """Flush + close every live scheduler (tests; process teardown).
    Idempotent — close() on an already-closed scheduler is a no-op — and
    registered via atexit so a process that never calls Store.close()
    (crashed test, REPL, signal-less exit) still drains in-flight lanes
    instead of abandoning their futures."""
    for sched in list(_schedulers):
        try:
            sched.close()
        # lint: allow-broad-except(atexit teardown must visit every
        # scheduler; one failed close must not strand the rest)
        except Exception:  # noqa: BLE001
            pass


atexit.register(shutdown_all)


def reconstruct_stacked_via_dict(coder, present_ids, stacked,
                                 data_only: bool = False):
    """Stacked-reconstruct contract implemented over the dict surface —
    THE single fallback shared by every layer (CPU mirror, AutoMeshCoder,
    scheduler, serving cascade): (missing_ids, rows[len(missing), B]).
    The dict path uses sorted-first-k survivor choice, matching the fused
    device matrix, so bytes are identical across all routes."""
    present_ids = tuple(present_ids)
    rec = (coder.reconstruct_data if data_only else coder.reconstruct)(
        {p: stacked[j] for j, p in enumerate(present_ids)})
    limit = coder.data_shards if data_only else coder.total_shards
    missing = tuple(i for i in range(limit) if i not in set(present_ids))
    if not missing:
        return (), np.zeros((0, stacked.shape[1]), np.uint8)
    return missing, np.stack(
        [np.asarray(rec[i], np.uint8) for i in missing])


def reconstruct_now(coder, present_ids, stacked,
                    data_only: bool = False, want=None):
    """Synchronous stacked reconstruct through the best available path:
    the shared scheduler when the dispatch plane is on (micro-batches
    with every concurrent caller), the coder's native stacked kernel
    otherwise, the dict form as a last resort. One cascade for every
    serving call site -> (missing_ids, rows).

    `want` (ISSUE 11) restricts the solve to those shard ids — the
    minimal-read repair form, where the survivor set may be smaller
    than k (an LRC local group) as long as it spans the wanted rows.

    When the caller is inside a trace span (a degraded S3 GET), the
    scheduler's per-slab attribution — queue wait, realized batch
    factor, chip — lands on that span: the per-request answer to "was
    this read slow because of the device or the queue". Under it, the
    span `ec.dispatch.wait` runs from submit until the rows are host
    bytes."""
    present_ids = tuple(present_ids)
    want = tuple(want) if want is not None else None
    sched = maybe_scheduler(coder)
    if sched is not None:
        sp = trace.current()
        with trace.span("ec.dispatch.wait", child_only=True):
            fut = sched.reconstruct_stacked(
                present_ids, stacked, data_only=data_only, want=want)
            missing, rows = fut.result()
            rows = np.asarray(rows)
        if sp is not None and fut.batch_slabs is not None:
            sp.set_attr(
                dispatchQueueWaitMs=round((fut.queue_wait_s or 0) * 1e3,
                                          3),
                dispatchBatchSlabs=fut.batch_slabs,
                dispatchChip=fut.chip)
        return missing, rows
    fn = getattr(coder, "reconstruct_stacked", None)
    if fn is not None:
        if want is not None:
            return fn(present_ids, stacked, data_only=data_only,
                      want=want)
        return fn(present_ids, stacked, data_only=data_only)
    if want is not None:
        raise TypeError(f"{type(coder).__name__} does not support "
                        f"minimal-read (want=) reconstruction")
    return reconstruct_stacked_via_dict(coder, present_ids, stacked,
                                        data_only)


class EcDispatchScheduler:
    """Window-batched stacked dispatch over one coder.

    Lanes (every key carries the coder's GEOMETRY id — ISSUE 11: stacked
    dispatches concatenate slabs along the byte axis and multiply ONE
    generator matrix, so slabs from different code geometries must never
    share a lane even if a coder is ever shared across geometries):
      ("enc", geom)                     — encode slabs [k, B] (single chip)
      ("enc", geom, chip)               — per-chip encode lane on a mesh
                                          coder: slabs round-robin across
                                          chips, each lane flushes as ONE
                                          device-affine stacked dispatch
      ("rec", geom, present_ids, data_only, want)
                                        — reconstruct slabs [P, B] sharing
                                          one survivor set / fused matrix
                                          (want = minimal-read targets);
                                          on a mesh the whole lane is
                                          pinned to the chip holding that
                                          set's decode matrix (LRU)

    Multi-chip (ISSUE 5): a fleet of concurrent encodes used to funnel
    through one stacked launch per window — per-chip lanes keep the V
    (volume/slab) axis spread over every chip's own dispatch queue, so
    the chips fill in parallel (RapidRAID's pipelined distribution,
    arXiv:1207.6744). Each chip lane is also SUBMITTED under its own
    chip's lock, so the chips' packs and launches overlap on the host
    too; only a launch that may span the mesh excludes them all.
    SWFS_EC_DISPATCH_VSHARD=0 restores the single funnel; single-device
    coders are untouched either way.
    """

    def __init__(self, coder, window: float | None = None,
                 max_slabs: int | None = None):
        self.coder = coder
        # geometry id baked into every lane key (ISSUE 11) — two coders
        # with identical (k, m) but different generator matrices (rs_10_4
        # vs lrc_10_2_2) must never stack into one device dispatch
        self.geom_id = getattr(coder, "geometry_id", None) or \
            f"rs_{coder.data_shards}_{coder.parity_shards}"
        self.window = window_s() if window is None else window
        self.max_slabs = max_slabs or int(
            os.environ.get("SWFS_EC_DISPATCH_MAX_SLABS",
                           str(DEFAULT_MAX_SLABS)))
        # lane state condition — witnessed (ISSUE 15): always acquired
        # AFTER the submission locks on the flush path, never before them
        self._cv = locks.wcondition("dispatch.lane_cv", rank=200)
        self._lanes: "OrderedDict[tuple, list[_Slab]]" = OrderedDict()
        # per-chip lane state — `_chips` resolves LAZILY on first submit:
        # asking a coder for its devices instantiates the backend (and
        # takes the chip), which only EC work should do. `_chip_mu` is
        # published with it: one submission lock per placement device.
        self._chips: list | None = None
        self._chip_mu: list = []
        # flushes between their pop and the end of their coder call
        # (guarded by _cv): what EC_DISPATCH_OVERLAPPED reads
        self._submitting = 0
        self._enc_rr = itertools.count()
        self._rec_chips: "OrderedDict[tuple, int]" = OrderedDict()
        self._rec_rr = 0
        self._rec_max = int(os.environ.get("SWFS_EC_DISPATCH_REC_SETS",
                                           str(DEFAULT_REC_SETS)))
        self._thread: threading.Thread | None = None
        # Submission locks: they serialize SUBMISSION into the coder, not
        # completion (jax dispatch stays async, so batches still pipeline
        # device-side). On the multi-device CPU mesh two concurrently
        # submitted shard_map modules interleave their cross-module
        # rendezvous and deadlock XLA (caught by tests/test_ec_pipeline.py
        # under the 8-device test mesh). A chip-pinned flush launches
        # single-device jits only, so it takes its chip's `_chip_mu` lock
        # alone and the chips are fed side by side. A flush that may
        # launch a multi-device module takes `_dispatch_mu` and then every
        # chip lock, in index order (`_flush_lane`). On a single-device
        # coder there are no chip locks: `_dispatch_mu` is its one lock.
        self._dispatch_mu = locks.wlock("dispatch.mu", rank=100)
        # host memory plane (ISSUE 12): lazily built so the env gate can
        # flip between A/B arms without rebuilding schedulers
        self._arena: StackArena | None = None
        self.closed = False
        _schedulers.add(self)

    # -- arena plumbing ----------------------------------------------------

    def _arena_for(self) -> StackArena | None:
        if not arena_enabled():
            return None
        arena = self._arena
        if arena is None:
            arena = self._arena = StackArena()
        return arena

    def _arena_release(self, buf, out_ref) -> None:
        if buf is not None and self._arena is not None:
            self._arena.release(buf, out_ref)

    def _arena_drop(self, buf) -> None:
        if buf is not None and self._arena is not None:
            self._arena.drop(buf)

    def _pack_wide(self, slabs: "list[_Slab]"):
        """Pack slabs column-compactly into ONE [rows, sum(widths)]
        buffer — an arena view when the plane is on, a fresh (never
        zero-filled) array otherwise. Columns are independent under
        every GF matmul this scheduler dispatches, so packing needs no
        inter-slab padding and therefore no memset at all: every byte
        of the packed region is slab payload."""
        rows = slabs[0].data.shape[0]
        total = sum(s.width for s in slabs)
        arena = self._arena_for()
        if arena is not None:
            buf = arena.get(rows * total)
            wide = buf.flat[: rows * total].reshape(rows, total)
        else:
            buf = None
            wide = np.empty((rows, total), np.uint8)
        off = 0
        for s in slabs:
            wide[:, off: off + s.width] = s.data
            off += s.width
        EC_DISPATCH_ZEROFILL_ELIDED.inc(rows * total)
        return wide, buf

    def _pack_vstack(self, slabs: "list[_Slab]"):
        """Pack slabs into ONE [V, rows, bmax] stack (the V-axis form
        mesh coders shard whole slabs across chips). Zero-fill is
        elided for the payload region — only ragged tails (width <
        bmax) are memset, and uniform-width batches memset nothing."""
        v = len(slabs)
        rows = slabs[0].data.shape[0]
        bmax = max(s.width for s in slabs)
        region = v * rows * bmax
        arena = self._arena_for()
        if arena is not None:
            buf = arena.get(region)
            stack = buf.flat[:region].reshape(v, rows, bmax)
        else:
            buf = None
            stack = np.empty((v, rows, bmax), np.uint8)
        tails = 0
        for i, s in enumerate(slabs):
            stack[i, :, : s.width] = s.data
            if s.width < bmax:
                stack[i, :, s.width:] = 0
                tails += rows * (bmax - s.width)
        EC_DISPATCH_ZEROFILL_ELIDED.inc(region - tails)
        return stack, buf

    # -- per-chip lane plumbing --------------------------------------------

    def _placement(self) -> list:
        """The coder's placement devices when it has per-chip entry
        points and more than one device, else []. Resolved once, with a
        submission lock per device (instantiates the backend — acceptable
        here: a submit IS device work, and a backend that fails raises to
        the submitter). Resolved whatever the env gate says, so the chip
        locks exist before any flush of this scheduler and stay fixed."""
        chips = self._chips
        if chips is None:
            chips = []
            fn = getattr(self.coder, "placement_devices", None)
            if fn is not None and hasattr(self.coder,
                                          "encode_parity_stacked_on"):
                devs = fn()
                if devs and len(devs) > 1:
                    chips = list(devs)
            with self._cv:
                if self._chips is None:  # first resolver publishes
                    self._chip_mu = [
                        locks.wlock(f"dispatch.chip{i}",
                                    rank=_CHIP_RANK0 + i)
                        for i in range(len(chips))]
                    self._chips = chips
                chips = self._chips
        return chips

    def _chip_list(self) -> list:
        """The placement devices when per-chip lanes apply, else []. The
        env gate is re-read every call so an A/B can flip V-axis sharding
        without rebuilding schedulers."""
        chips = self._placement()
        return chips if vshard_enabled() else []

    def _assign_rec_chip(self, key: tuple, n_chips: int) -> int:
        """Stable survivor-set -> chip placement, LRU-evicted: every slab
        sharing this fused decode matrix dispatches on the chip where the
        matrix is resident (rs_jax keeps it cached device-side)."""
        with self._cv:
            got = self._rec_chips.get(key)
            if got is None:
                got = self._rec_rr % n_chips
                self._rec_rr += 1
                self._rec_chips[key] = got
                while len(self._rec_chips) > self._rec_max:
                    # evict oldest set WITHOUT queued slabs: dropping an
                    # in-flight lane's pinning mid-window would dispatch
                    # it unpinned and desync the per-chip counters
                    for old in self._rec_chips:
                        if old not in self._lanes:
                            del self._rec_chips[old]
                            break
                    else:
                        break  # every set in flight; defer eviction
            else:
                self._rec_chips.move_to_end(key)
            return got

    def _lane_chip(self, key: tuple) -> int | None:
        """Chip index a lane is pinned to (None = single-chip path)."""
        if key[0] == "enc":
            return key[2] if len(key) > 2 else None
        with self._cv:
            return self._rec_chips.get(key)

    def _mesh_wide(self, key: tuple, chip: int | None) -> bool:
        """Whether a flush of this lane may launch a multi-device module:
        every lane with no chip (on a mesh coder the V-stacked or
        column-split launch), and a pinned reconstruct lane whose backlog
        already fills the mesh (`_dispatch_reconstruct` may shard it).
        Read before the flush pops the lane; the pop never widens what
        was decided here (`_dispatch_reconstruct` only shards a batch
        flushed mesh-wide)."""
        if chip is None:
            return True
        if key[0] != "rec" or not hasattr(
                self.coder, "reconstruct_stacked_vsharded"):
            return False
        with self._cv:
            return len(self._lanes.get(key, ())) >= len(self._chip_mu)

    # -- submission --------------------------------------------------------

    def encode_parity(self, data: np.ndarray, copy: bool = True) -> EcFuture:
        """Submit one [k, B] slab; the future resolves to parity [m, B].

        `copy=True` (default) snapshots the slab, for callers that do
        not hold their buffer until the future resolves (its bytes
        count in `SeaweedFS_ec_dispatch_submit_copied_bytes{lane}`).
        The encode file pipeline holds each slab's buffer until its
        parity is host bytes and submits with `copy=False`.

        On a mesh coder, slabs round-robin over per-chip lanes — one
        pipeline alone fans across every chip, and N pipelines load the
        chips evenly (no chip starves; tests pin the fairness)."""
        data = np.asarray(data, dtype=np.uint8)
        if copy:
            data = data.copy()
            EC_DISPATCH_SUBMIT_COPIED.inc(data.nbytes, lane="encode")
        chips = self._chip_list()
        if chips:
            key = ("enc", self.geom_id, next(self._enc_rr) % len(chips))
        else:
            key = ("enc", self.geom_id)
        return self._submit(key, data, chip=self._lane_chip(key))

    def reconstruct_stacked(self, present_ids, stacked: np.ndarray,
                            data_only: bool = False,
                            copy: bool = False, want=None) -> EcFuture:
        """Submit survivors [P, B] (caller row order); the future resolves
        to (missing_ids, rows[len(missing), B]). Slabs sharing a survivor
        set (and minimal-read target set `want`) share one
        column-concatenated `reconstruct_stacked` dispatch, pinned to the
        set's assigned chip on a mesh coder."""
        stacked = np.asarray(stacked, dtype=np.uint8)
        if copy:
            stacked = stacked.copy()
            EC_DISPATCH_SUBMIT_COPIED.inc(stacked.nbytes,
                                          lane="reconstruct")
        if want is not None and not hasattr(self.coder,
                                            "reconstruct_stacked"):
            raise TypeError(
                f"{type(self.coder).__name__} does not support "
                f"minimal-read (want=) reconstruction")
        key = ("rec", self.geom_id, tuple(present_ids), bool(data_only),
               tuple(want) if want is not None else None)
        chips = self._chip_list()
        chip = self._assign_rec_chip(key, len(chips)) if chips else None
        return self._submit(key, stacked, chip=chip)

    def _submit(self, key: tuple, data: np.ndarray,
                chip: int | None = None) -> EcFuture:
        fut = EcFuture(self, key)
        slab = _Slab(data, fut)
        kind = "encode" if key[0] == "enc" else "reconstruct"
        EC_DISPATCH_SLABS.inc(lane=kind,
                              chip="-" if chip is None else str(chip))
        with self._cv:
            if self.closed:
                raise RuntimeError("ec dispatch scheduler is closed")
            lane = self._lanes.get(key)
            if lane is None:
                lane = self._lanes[key] = []
            lane.append(slab)
            full = len(lane) >= self.max_slabs
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="ec-dispatch-flusher",
                    daemon=True)
                self._thread.start()
            self._cv.notify_all()
        if full:
            # cap reached: dispatch on the submitter rather than queueing
            # unboundedly behind the window
            self._demand_flush(key)
        return fut

    # -- flushing ----------------------------------------------------------

    def _run(self) -> None:
        # NUMA-affine flush path (ISSUE 12): the flusher packs arenas
        # and feeds the device driver — pin it to one node's CPUs so
        # every pack/commit pass stays on local memory. No-op unless
        # SWFS_EC_DISPATCH_PIN=1 (utils/numa.py; fails soft on hosts
        # without /sys topology or sched_setaffinity).
        numa.pin_thread()
        idle_since: float | None = None
        while True:
            with self._cv:
                now = time.perf_counter()
                if self.closed:
                    return
                if not self._lanes:
                    if idle_since is None:
                        idle_since = now
                    elif now - idle_since > _IDLE_EXIT_S:
                        # self-clean: nothing pending for a while
                        if self._thread is threading.current_thread():
                            self._thread = None
                        return
                    self._cv.wait(_IDLE_EXIT_S / 4)
                    continue
                idle_since = None
                deadline = min(l[0].t for l in self._lanes.values()) \
                    + self.window
                if now < deadline:
                    self._cv.wait(deadline - now)
                    continue
                due = [(k, l[0].ctx) for k, l in self._lanes.items()
                       if l[0].t + self.window <= now]
            # elevator batching (same shape as the PR-2 group commit):
            # take the submission lock FIRST, re-pop after acquiring it —
            # every slab that arrived while the previous dispatch was in
            # flight rides this one instead of fragmenting into its own
            for k, ctx in due:
                self._flush_lane(k, parent=ctx)

    def _demand_flush(self, key: tuple) -> None:
        self._flush_lane(key)

    def _flush_lane(self, key: tuple, parent=None) -> None:
        """Dispatch the lane's slabs under the submission locks its
        launch needs: a chip-pinned lane takes its chip's lock and
        nothing else, so the chips' flushes overlap while each chip's
        launches keep lane order; a flush that may launch a multi-device
        module (`_mesh_wide`) takes `_dispatch_mu`, then every chip's
        lock in index order, so nothing else of this scheduler is
        submitted meanwhile. The wait for the lock or locks is the span
        `ec.dispatch.lock_wait` and
        `SeaweedFS_ec_dispatch_lock_wait_seconds{lane, chip}`: a child
        of `parent` (the flusher passes the lane's first slab's span),
        else of the flushing thread's span. A batch whose submission
        starts while another chip's is under way counts in
        `SeaweedFS_ec_dispatch_overlapped_batches{lane, chip}`."""
        kind = "encode" if key[0] == "enc" else "reconstruct"
        chip = self._lane_chip(key)
        label = "-" if chip is None else str(chip)
        mesh_wide = self._mesh_wide(key, chip)
        held = ([self._dispatch_mu, *self._chip_mu] if mesh_wide
                else [self._chip_mu[chip]])
        with trace.timed("ec.dispatch.lock_wait", parent=parent,
                         child_only=True, lane=kind, chip=label) as waited:
            for mu in held:
                mu.acquire()
        EC_DISPATCH_LOCK_WAIT.inc(waited.seconds, lane=kind, chip=label)
        try:
            with self._cv:
                slabs = self._lanes.pop(key, None)
                overlapped = self._submitting > 0
                if slabs:
                    self._submitting += 1
            if not slabs:
                return
            if overlapped:
                EC_DISPATCH_OVERLAPPED.inc(lane=kind, chip=label)
            try:
                self._dispatch(key, slabs, kind, chip, mesh_wide)
            finally:
                with self._cv:
                    self._submitting -= 1
        finally:
            for mu in reversed(held):
                mu.release()

    def flush(self) -> None:
        """Dispatch every pending lane now (tests; close)."""
        while True:
            with self._cv:
                keys = list(self._lanes)
            if not keys:
                return
            for k in keys:
                self._flush_lane(k)

    def _dispatch(self, key: tuple, slabs: list[_Slab], kind: str,
                  chip: int | None, mesh_wide: bool) -> None:
        now = time.perf_counter()
        # a pinned lane runs on its chip even if the env gate flipped
        # since it was queued: it holds that chip's lock, not the mesh's
        device = self._chips[chip] if chip is not None else None
        label = "-" if chip is None else str(chip)
        EC_DISPATCH_BATCHES.inc(lane=kind, chip=label,
                                reason=self._lane_reason(device))
        EC_DISPATCH_STACK_SLABS.observe(len(slabs), lane=kind)
        nbytes = sum(s.data.nbytes for s in slabs)
        EC_DISPATCH_STACK_BYTES.observe(nbytes, lane=kind)
        for s in slabs:
            EC_DISPATCH_WINDOW_WAIT.observe(now - s.t, lane=kind,
                                            chip=label)
            # trace attribution, readable off the future after result()
            s.fut.queue_wait_s = now - s.t
            s.fut.batch_slabs = len(slabs)
            s.fut.chip = label
        # caller holds this lane's submission locks (`_flush_lane`): a
        # chip lane's lock alone — per-chip sub-dispatches are plain
        # per-device jits, so the chips' packs and launches overlap —
        # or every lock for a launch that may span the mesh (concurrent
        # shard_map submissions deadlock XLA's cross-module rendezvous
        # on the multi-device CPU mesh). In-flight dispatch time turns
        # into batching for the lane's next elevator. The batch span
        # times the pack and the coder call on this (the flushing) thread.
        with trace.span(f"ec.dispatch.{kind}", parent=slabs[0].ctx,
                        child_only=True, slabs=len(slabs),
                        bytes=nbytes, chip=label):
            try:
                if key[0] == "enc":
                    self._dispatch_encode(slabs, device)
                else:
                    self._dispatch_reconstruct(key, slabs, device,
                                               mesh_wide)
            except BaseException as e:
                for s in slabs:
                    if not s.fut.done():
                        s.fut._set_error(e)

    def _lane_reason(self, device) -> str:
        """WHY this lane dispatched where it did — the `reason` label on
        EC_DISPATCH_BATCHES (ISSUE 17 satellite): chip_affine = pinned to
        a placement device; cpu_env / cpu_explicit = host coder (pinned
        by SEAWEEDFS_TPU_CODER vs constructed by the call site,
        models/coder.py stamps which — HOST_REASONS); vshard_off =
        per-chip lanes gated off by env; otherwise single_device (one
        accelerator — no chip lanes to pin)."""
        if device is not None:
            return "chip_affine"
        reason = getattr(self.coder, "backend_reason", None)
        if reason:
            return reason
        if not vshard_enabled():
            return "vshard_off"
        return "single_device"

    def _host_encode(self, wide: np.ndarray) -> np.ndarray:
        """Host-CPU encode of a column-compact [k, W] view: compiled
        XOR-schedule path (ops/rs_sched.py) when the gate is on and the
        schedule's predicted cost beats the dense matmul, else the dense
        coder path. Bit-identical either way — rs_cpu is the oracle the
        schedule tests pin against."""
        out = rs_sched.maybe_encode(self.coder, wide)
        if out is not None:
            return out
        return self.coder.encode_parity(wide)

    def _dispatch_encode(self, slabs: list[_Slab], device=None) -> None:
        fn_on = (getattr(self.coder, "encode_parity_stacked_on", None)
                 if device is not None else None)
        fn_wide_on = (getattr(self.coder, "encode_parity_on", None)
                      if device is not None else None)
        if len(slabs) == 1:
            # lone slab: NO stack copy on ANY lane (ISSUE 12 satellite —
            # PR 5 gave chip lanes the [None] view; non-chip lanes now
            # share the same direct 2-D dispatch, and chip lanes with
            # the wide entry skip even the [None] wrapper)
            s = slabs[0]
            if fn_wide_on is not None:
                out0 = fn_wide_on(s.data, device)
            elif fn_on is not None:
                out0 = fn_on(s.data[None], device)[0]
            else:
                out0 = self._host_encode(s.data)
            s.fut._set(out0)
            return
        if not hasattr(self.coder, "encode_parity_stacked"):
            for s in slabs:  # exotic coder: amortization off, bytes same
                out0 = self.coder.encode_parity(s.data)
                s.fut._set(out0)
            return
        if getattr(self.coder, "prefers_vstack", False) and device is None:
            # mesh coder, non-chip lane: keep the [V, k, B] form so the
            # backend can shard WHOLE slabs across chips (ISSUE 5) —
            # packed into a recycled arena buffer, ragged tails only
            stack, buf = self._pack_vstack(slabs)
            try:
                out = self.coder.encode_parity_stacked(stack)
            except BaseException:
                self._arena_drop(buf)
                raise
            # ragged tails ride zero-padded columns; zero columns encode
            # to zero parity and are sliced away, so per-slab bytes are
            # identical to a lone dispatch (tests/test_ec_dispatch.py)
            for i, s in enumerate(slabs):
                s.fut._set(out[i][:, : s.width])
            self._arena_release(buf, out)
            return
        # wide (column-compact) packing: the V slabs lie side by side in
        # ONE [k, sum(widths)] arena view — no [V, k, B] allocation, no
        # zero-fill, and no transpose/reshape copy inside the backend
        # (parity is a per-byte-column GF matmul, so the wide form IS
        # what every stacked kernel reduces to internally)
        wide, buf = self._pack_wide(slabs)
        try:
            if fn_wide_on is not None:
                # device-affine sub-dispatch: this chip lane's slabs
                # ride one wide launch pinned to the lane's chip
                out = fn_wide_on(wide, device)
            elif fn_on is not None:
                # older device-affine coder without the wide entry: the
                # [None] stacked view (V=1), still no extra copy
                out = fn_on(wide[None], device)[0]
            else:
                out = self._host_encode(wide)
        except BaseException:
            self._arena_drop(buf)
            raise
        off = 0
        for s in slabs:
            s.fut._set(out[:, off: off + s.width])
            off += s.width
        self._arena_release(buf, out)

    def _dispatch_reconstruct(self, key: tuple, slabs: list[_Slab],
                              device, mesh_wide: bool) -> None:
        _, _geom, present_ids, data_only, want = key
        if not hasattr(self.coder, "reconstruct_stacked"):
            for s in slabs:  # exotic coder: per-slab dict reconstruct
                out0 = reconstruct_stacked_via_dict(
                    self.coder, present_ids, s.data, data_only)
                s.fut._set(out0)
            return
        chips = self._chip_list()
        fn_v = getattr(self.coder, "reconstruct_stacked_vsharded", None)
        if (mesh_wide and fn_v is not None and chips
                and len(slabs) >= len(chips)
                and len({s.width for s in slabs}) == 1):
            # a BIG uniform batch (a rebuild pipeline's demand-flushed
            # backlog) outgrows its single assigned chip: shard the V
            # axis over the whole mesh instead, so a lone rebuild uses
            # every chip (small serving micro-batches keep the
            # survivor-set chip placement below, and so does a batch
            # flushed under its chip's lock alone). `want` (the rebuild's
            # minimal-read form) rides through — it must not demote the
            # rebuild workload to a single chip. Uniform widths mean the
            # arena pack memsets NOTHING (every byte is payload).
            vstack, buf = self._pack_vstack(slabs)
            try:
                missing, rows = fn_v(present_ids, vstack,
                                     data_only=data_only,
                                     **({} if want is None
                                        else {"want": want}))
            except BaseException:
                self._arena_drop(buf)
                raise
            for i, s in enumerate(slabs):
                s.fut._set((missing, rows[i]))
            self._arena_release(buf, rows)
            return
        fn_on = (getattr(self.coder, "reconstruct_stacked_on", None)
                 if device is not None else None)

        def recon(stk):
            kw = {} if want is None else {"want": want}
            if fn_on is not None:
                # survivor-set chip placement: the fused decode matrix is
                # resident on this lane's chip; its slabs dispatch there
                return fn_on(present_ids, stk, data_only=data_only,
                             device=device, **kw)
            # host lane: compiled XOR schedule of the fused repair
            # matrix when it beats the dense solve (ops/rs_sched.py) —
            # same survivor-subset choice, bit-identical rows
            got = rs_sched.maybe_reconstruct(
                self.coder, present_ids, stk, data_only=data_only,
                want=want)
            if got is not None:
                return got
            return self.coder.reconstruct_stacked(
                present_ids, stk, data_only=data_only, **kw)

        if len(slabs) == 1:
            out0 = recon(slabs[0].data)
            slabs[0].fut._set(out0)
            return
        # column-concatenation into a recycled arena view (the old
        # np.concatenate allocated a fresh buffer per flush)
        wide, buf = self._pack_wide(slabs)
        try:
            missing, rows = recon(wide)
        except BaseException:
            self._arena_drop(buf)
            raise
        off = 0
        for s in slabs:
            s.fut._set((missing, rows[:, off: off + s.width]))
            off += s.width
        self._arena_release(buf, rows)

    # -- lifecycle / introspection ----------------------------------------

    def pending(self) -> int:
        with self._cv:
            return sum(len(l) for l in self._lanes.values())

    def arena_stats(self) -> dict:
        """Live arena snapshot for /status (zeros when the plane is off
        or this scheduler has never flushed a multi-slab batch)."""
        arena = self._arena
        return arena.stats() if arena is not None else {
            "pooled": 0, "pooledBytes": 0, "inUseBytes": 0,
            "quarantined": 0}

    def chip_depths(self) -> dict[str, int]:
        """Queued slabs per chip lane ("-" = single-chip lanes) — the
        per-chip depth surfaced in the volume server's /status."""
        with self._cv:
            out: dict[str, int] = {}
            for key, lane in self._lanes.items():
                if key[0] == "enc" and len(key) > 2:
                    c = str(key[2])
                elif key[0] == "rec":
                    idx = self._rec_chips.get(key)
                    c = "-" if idx is None else str(idx)
                else:
                    c = "-"
                out[c] = out.get(c, 0) + len(lane)
            return out

    def close(self) -> None:
        """Drain pending lanes, then stop + join the flusher thread.

        Idempotent: a second close (Store.close after shutdown_all, a
        test tearing down twice) neither re-drains nor re-joins — and
        never joins the calling thread itself, so a close reached from
        a future callback can't deadlock on a dead flusher."""
        with self._cv:
            already = self.closed
            self.closed = True  # rejects NEW submissions while we drain
            t = self._thread
            self._thread = None
            self._cv.notify_all()
        if not already:
            self.flush()  # resolve every already-queued future
        if t is not None and t is not threading.current_thread() \
                and t.is_alive():
            t.join(timeout=5)
        arena = self._arena
        if arena is not None:
            arena.close()


# -- reconstructed-interval cache (degraded-read serving side) --------------

DEFAULT_CACHE_BLOCK = 256 * 1024  # the reference's own EC buffer size
DEFAULT_CACHE_MB = 32


class ReconstructIntervalCache:
    """Bounded LRU of reconstructed shard blocks.

    Key: (vid, shard_id, block_index) over fixed-size blocks of the
    shard's byte space — a hot lost shard pays the k-survivor fetch +
    dispatch once per block, and every later degraded read of any needle
    in that block is served from memory. MUST be invalidated whenever a
    shard's backing files can change: mount/unmount/delete
    (server/volume.py wires those; the chaos suite proves it)."""

    def __init__(self, max_bytes: int | None = None,
                 block_size: int | None = None):
        if max_bytes is None:
            max_bytes = int(float(os.environ.get(
                "SWFS_EC_RECON_CACHE_MB", str(DEFAULT_CACHE_MB)))
                * 1024 * 1024)
        if block_size is None:
            block_size = int(os.environ.get("SWFS_EC_RECON_CACHE_BLOCK",
                                            str(DEFAULT_CACHE_BLOCK)))
        self.max_bytes = max_bytes
        self.block_size = max(1, block_size)
        self._entries: "OrderedDict[tuple, bytes]" = OrderedDict()
        self._bytes = 0
        # per-vid invalidation generation: a put computed from shard
        # state observed BEFORE an invalidate must not repopulate the
        # cache after it (reconstruct-vs-remount TOCTOU)
        self._gens: dict[int, int] = {}
        self._lock = locks.wlock("dispatch.recon_cache", rank=810)

    def enabled(self) -> bool:
        return self.max_bytes > 0

    def blocks_for(self, offset: int, size: int) -> range:
        """Block indices covering [offset, offset+size)."""
        if size <= 0:
            return range(0)
        return range(offset // self.block_size,
                     (offset + size - 1) // self.block_size + 1)

    def get(self, vid: int, sid: int, block: int) -> bytes | None:
        with self._lock:
            got = self._entries.get((vid, sid, block))
            if got is not None:
                self._entries.move_to_end((vid, sid, block))
        EC_RECON_CACHE_COUNTER.inc(result="hit" if got is not None
                                   else "miss")
        return got

    def generation(self, vid: int) -> int:
        """Snapshot BEFORE gathering survivors; pass to put() so a
        reconstruct that straddles an invalidate can't repopulate the
        cache with pre-invalidation shard bytes."""
        with self._lock:
            return self._gens.get(vid, 0)

    def put(self, vid: int, sid: int, block: int, data: bytes,
            gen: int | None = None) -> None:
        if not self.enabled() or len(data) > self.max_bytes:
            return
        key = (vid, sid, block)
        with self._lock:
            if gen is not None and self._gens.get(vid, 0) != gen:
                return  # invalidated while we were reconstructing
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= len(old)
            self._entries[key] = data
            self._bytes += len(data)
            while self._bytes > self.max_bytes and self._entries:
                _, evicted = self._entries.popitem(last=False)
                self._bytes -= len(evicted)
                EC_RECON_CACHE_COUNTER.inc(result="evict")
        EC_RECON_CACHE_COUNTER.inc(result="put")

    def invalidate(self, vid: int, sid: int | None = None) -> int:
        """Drop every block of `vid` (optionally one shard). Returns the
        number of entries dropped."""
        with self._lock:
            self._gens[vid] = self._gens.get(vid, 0) + 1
            doomed = [k for k in self._entries
                      if k[0] == vid and (sid is None or k[1] == sid)]
            for k in doomed:
                self._bytes -= len(self._entries.pop(k))
        if doomed:
            EC_RECON_CACHE_COUNTER.inc(len(doomed), result="invalidate")
        return len(doomed)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)
