"""Shared bounded fan-out executor for the data-plane hot paths (ISSUE 14).

`VolumeServer.replicate_write` used to construct a fresh
ThreadPoolExecutor per replicated write — thread spawn + teardown on the
hottest write path, measured at tens of microseconds per call on the
PR-2 syscall-diet box. The pipelined chunk engine (filer GET readahead +
PUT upload overlap) needs the same kind of bounded concurrency, so both
now share ONE process-wide executor whose threads park between calls.

Bounds: `SWFS_FANOUT_THREADS` (default 16) caps concurrent tasks PER
POOL. Pools are NAMED TIERS, not one flat budget, because tasks in one
tier may transitively depend on another tier making progress: a filer
`save_chunk` upload blocks on a volume PUT handler whose replication
fan-out needs threads of its own. In a combined-process topology
(`weed server -filer`, the chaos fixture) a single shared pool full of
blocked uploads would starve the very replica sends those uploads wait
on — a circular wait. The tiers form a DAG instead:

    "pipeline"  (GET prefetch / PUT upload windows)
        └─ blocks on volume handlers, which fan out on →
    "replicate" (replica sends)
        └─ blocks on replica handlers, which fan out on nothing
    "ec_read"   (EC file pipeline slab fills, storage/ec_files.py: one
                 worker per CPU) — local positional reads, blocks on nothing

so saturation in one tier can never deadlock the tier below it. Tasks
must never submit into their OWN pool (the classic shared-pool
deadlock); every consumer bottoms out in socket IO.

`tools/lint.py` rule SWFS003 enforces the contract: new bare
`ThreadPoolExecutor(` construction inside `seaweedfs_tpu/server/` or
`seaweedfs_tpu/filer/` is a lint error unless the site carries an
explicit `lint: allow-executor` justification.
"""

from __future__ import annotations

import atexit
import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor

from .locks import wlock

_lock = wlock("fanout.mu", rank=860)
_executors: dict[str, ThreadPoolExecutor] = {}


def _threads() -> int:
    try:
        return max(4, int(os.environ.get("SWFS_FANOUT_THREADS", "16")))
    except ValueError:
        return 16


def _shutdown() -> None:
    with _lock:
        pools = list(_executors.values())
        _executors.clear()
    for ex in pools:
        ex.shutdown(wait=False, cancel_futures=True)


def executor(pool: str = "pipeline",
             max_workers: int | None = None) -> ThreadPoolExecutor:
    """The process-wide fan-out executor for `pool` (created on first
    use, with `max_workers` threads, by default SWFS_FANOUT_THREADS).
    Pick the tier that matches what the task BLOCKS ON — see the module
    docstring's dependency DAG."""
    ex = _executors.get(pool)
    if ex is not None:
        return ex
    with _lock:
        ex = _executors.get(pool)
        if ex is None:
            if not _executors:
                atexit.register(_shutdown)
            ex = _executors[pool] = ThreadPoolExecutor(
                max_workers=max_workers or _threads(),
                thread_name_prefix=f"swfs-fanout-{pool}")
        return ex


def submit(fn, *args, pool: str = "pipeline", **kwargs) -> Future:
    return executor(pool).submit(fn, *args, **kwargs)


def run_all(fn, items, pool: str = "pipeline",
            max_workers: int | None = None) -> list:
    """Run `fn(item)` for every item concurrently; wait for ALL to
    settle, then raise the first failure (in item order). Waiting before
    raising matters for replication fan-out: an early raise would leave
    sends still holding the request body and the caller unable to tell
    which replicas actually received it."""
    ex = executor(pool, max_workers)
    return gather([ex.submit(fn, it) for it in items])


def gather(futs: list[Future]) -> list:
    """The results of `futs` in order, once ALL have settled; then the
    first failure, if any, is raised (see run_all)."""
    first_err: BaseException | None = None
    results = []
    for f in futs:
        try:
            results.append(f.result())
        except BaseException as e:  # noqa: BLE001 — re-raised below
            results.append(None)
            if first_err is None:
                first_err = e
    if first_err is not None:
        raise first_err
    return results
