"""Closed-loop EC conversions through the volume server's gRPC handlers.

A round encodes every volume of the configuration at once
(VolumeEcShardsGenerate, what `ec.encode` calls) and, where the mix says
"rebuild", deletes the configuration's lost shards and rebuilds them
(VolumeEcShardsRebuild, what `ec.rebuild` calls); then the EC files are
dropped so that the next round encodes again. The window holds a fixed
number of rounds, due at even steps over its seconds, so that every run
does the same work and writes the same bytes (a round writes every shard
file of every volume); a round still in flight at the close completes.

Host-clock records: every call's (start, end, bytes). Checked: the
sha256 of every shard file each call wrote, after the window, against
the plain reference over the same .dat.

Traffic keys: "rebuild" (bool), "rounds" (to the window),
"warmup_rounds", "trace_rounds".

Besides, a line gives the bytes of the files the run wrote (volumes,
warm-up rounds, window), read from their sizes: a machine's disk writes
bound how many rounds a run can hold.
"""

from __future__ import annotations

import glob
import os
import time
from concurrent.futures import ThreadPoolExecutor

import cluster
import dataset
import reference
import volbuild


def _geometry(cfg: dict) -> tuple[int, int, int, int]:
    return (cfg["data_shards"], cfg["parity_shards"], cfg["large_block"],
            cfg["small_block"])


def setup(run) -> None:
    from seaweedfs_tpu.pb import volume_server_pb2 as vs

    cfg = run.config
    vids = list(range(1, cfg["volumes"] + 1))
    t0 = time.perf_counter()
    dat = {}
    for vid in vids:
        objs = dataset.objects(cfg, run.seed, vid - 1)
        dat[vid] = volbuild.write_volume(
            os.path.join(run.workdir, str(vid)), objs,
            mime=cfg.get("mime", "").encode(),
            last_modified=cfg["last_modified"],
            append_at_ns=cfg["last_modified"] * 10**9)
        del objs
    _wrote(run, "volumes", [os.path.join(run.workdir, f"{vid}.{ext}")
                            for vid in vids for ext in ("dat", "idx")])
    run.say(f"volumes: {len(vids)} x {dat[vids[0]]} bytes built from the "
            f"seed in {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    c = cluster.start(run.workdir, max_volumes=len(vids))
    t_servers = time.perf_counter() - t0
    run.state.update(cluster=c, vids=vids, dat=dat, digests=[],
                     encodes=[], rebuilds=[], pool=ThreadPoolExecutor(
                         max(len(vids), 4) * 4))
    for vid in vids:
        c.stub.VolumeMarkReadonly(
            vs.VolumeMarkReadonlyRequest(volume_id=vid), timeout=60)
    t0 = time.perf_counter()
    for _ in range(run.traffic["warmup_rounds"]):
        _round(run, record=False)
    run.say(f"servers started in {t_servers:.2f} s; warm-up rounds "
            f"{time.perf_counter() - t0:.2f} s")
    if run.chips > 1:
        t0 = time.perf_counter()
        widths = _warm_encode_lanes(c, run.config["data_shards"],
                                    run.config["small_block"])
        run.say(f"encode batches warmed in {time.perf_counter() - t0:.2f} "
                f"s, widths by chip: {widths}")


def _warm_encode_lanes(c, k: int, block: int) -> dict:
    """Form every encode batch width on every chip before the window.

    On several chips the scheduler deals encode slabs round-robin over
    per-chip lanes, and each (chip, width) pair is a program of its own;
    how many slabs meet in a batch depends on timing, so warm-up rounds
    leave pairs that then compile inside the window. 4n zero slabs
    submitted at once give every chip a batch of n, for n up to the
    scheduler's slab cap. Returns {chip: widths formed}."""
    import numpy as np
    from seaweedfs_tpu.ops import dispatch

    sched = dispatch.scheduler_for(c.volume.store.coder)
    chips = len(c.volume.store.coder.placement_devices())
    zero = np.zeros((k, block), np.uint8)
    formed: dict = {}
    for n in range(1, sched.max_slabs + 1):
        futs = [sched.encode_parity(zero, copy=False)
                for _ in range(chips * n)]
        for f in futs:
            f.result()
            formed.setdefault(str(f.chip), set()).add(f.batch_slabs)
    return {chip: sorted(w) for chip, w in sorted(formed.items())}


def _wrote(run, phase: str, paths) -> None:
    """Add the sizes of files the run just wrote to its phase's count."""
    n = sum(os.path.getsize(p) for p in paths if os.path.exists(p))
    w = run.state.setdefault("written", {})
    w[phase] = w.get(phase, 0) + n


def _call(run, name: str, fn, record: list | None, nbytes: int) -> bool:
    """One timed call; a call that fails counts as failed and adds no
    work to the rate (its shards then read as missing)."""
    import grpc

    with run.annotate(name):
        t0 = time.perf_counter()
        try:
            fn()
        except grpc.RpcError as e:
            run.say(f"{name} failed: {e.code()}")
            run.failed += record is not None
            return False
        t1 = time.perf_counter()
    if record is not None:
        record.append((t0, t1, nbytes))
    return True


def _hash_files(pool, paths: list[str]) -> list[str]:
    def one(p):
        try:
            return reference.file_digest(p)
        except FileNotFoundError:
            return "missing"
    return list(pool.map(one, paths))


def _round(run, record: bool) -> None:
    from seaweedfs_tpu.pb import volume_server_pb2 as vs

    st = run.state
    c, vids, pool = st["cluster"], st["vids"], st["pool"]
    k, m, _, _ = _geometry(run.config)
    lost = list(run.config["lost_shards"])
    base = {vid: os.path.join(run.workdir, str(vid)) for vid in vids}

    def encode(vid):
        _call(run, "encode", lambda: c.stub.VolumeEcShardsGenerate(
            vs.VolumeEcShardsGenerateRequest(volume_id=vid), timeout=600),
            st["encodes"] if record else None, st["dat"][vid])

    for f in [pool.submit(encode, vid) for vid in vids]:
        f.result()
        run.attempted += record
    phase = "window" if record else "warm-up"
    _wrote(run, phase, [p for vid in vids
                        for p in glob.glob(f"{base[vid]}.ec*")
                        + [f"{base[vid]}.vif"]])
    if record:
        for vid in vids:
            paths = [f"{base[vid]}.ec{i:02d}" for i in range(k + m)]
            st["digests"].append((vid, list(range(k + m)),
                                  _hash_files(pool, paths)))
    if run.traffic["rebuild"]:
        for vid in vids:
            c.stub.VolumeEcShardsDelete(vs.VolumeEcShardsDeleteRequest(
                volume_id=vid, shard_ids=lost), timeout=60)
        kept = next(i for i in range(k + m) if i not in lost)
        shard_bytes = {vid: os.path.getsize(f"{base[vid]}.ec{kept:02d}")
                       if os.path.exists(f"{base[vid]}.ec{kept:02d}") else 0
                       for vid in vids}

        def rebuild(vid):
            got = []

            def call():
                resp = c.stub.VolumeEcShardsRebuild(
                    vs.VolumeEcShardsRebuildRequest(volume_id=vid),
                    timeout=600)
                got.extend(resp.rebuilt_shard_ids)
            _call(run, "rebuild", call, st["rebuilds"] if record else None,
                  shard_bytes[vid] * len(lost))
            return got

        for vid, f in [(vid, pool.submit(rebuild, vid)) for vid in vids]:
            got = f.result()
            _wrote(run, phase, [f"{base[vid]}.ec{i:02d}" for i in lost])
            if record:
                run.attempted += 1
                run.failed += bool(got) and sorted(got) != sorted(lost)
                paths = [f"{base[vid]}.ec{i:02d}" for i in lost]
                st["digests"].append((vid, lost, _hash_files(pool, paths)))
    for vid in vids:
        c.stub.VolumeEcShardsDelete(vs.VolumeEcShardsDeleteRequest(
            volume_id=vid, shard_ids=list(range(k + m))), timeout=60)


def window(run) -> None:
    if run.trace:
        for _ in range(run.traffic["trace_rounds"]):
            _round(run, record=True)
        return
    # a fixed number of rounds, round i due at i/rounds of the window
    t0 = time.perf_counter()
    rounds = run.traffic["rounds"]
    for i in range(rounds):
        wait = t0 + i * run.seconds / rounds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _round(run, record=True)
    wait = t0 + run.seconds - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


def teardown(run) -> None:
    st = run.state
    if "cluster" in st:
        st["cluster"].stop()
    if "pool" in st:
        st["pool"].shutdown()
    enc, reb = st.get("encodes", []), st.get("rebuilds", [])
    run.host.update(encodes=enc, rebuilds=reb)
    run.host.setdefault("lines", []).append(
        f"calls in the window: {len(enc)} encodes "
        f"{[round(b - a, 3) for a, b, _ in enc]} s, {len(reb)} rebuilds "
        f"{[round(b - a, 3) for a, b, _ in reb]} s")
    w = st.get("written", {})
    run.host["lines"].append(
        "bytes written to files, GB: " + ", ".join(
            f"{k} {v / 1e9:.3f}" for k, v in w.items())
        + f"; in all {sum(w.values()) / 1e9:.3f}")


def check(run) -> None:
    """Every shard the window's calls wrote against the reference."""
    k, m, large, small = _geometry(run.config)
    want = {}
    t0 = time.perf_counter()
    for vid in run.state["vids"]:
        want[vid] = reference.shard_digests(
            os.path.join(run.workdir, f"{vid}.dat"), k, m, large, small)
    wrong = 0
    compared = 0
    for vid, shards, got in run.state["digests"]:
        for sid, digest in zip(shards, got):
            compared += 1
            wrong += digest != want[vid][sid]
    run.host["lines"].append(
        f"reference: {compared} shard files compared in "
        f"{time.perf_counter() - t0:.2f} s")
    # a window that wrote no shard file proves nothing: it counts as wrong
    run.check("shards_wrong", wrong if compared else 1, 0)
