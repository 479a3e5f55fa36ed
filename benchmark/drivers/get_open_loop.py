"""Open-loop GETs against an EC volume, from a child process.

Set-up builds the configuration's volume from the seed, converts it to EC
on the device (VolumeEcShardsGenerate), serves it from its shards and,
where the configuration names lost shards and the mix is degraded,
deletes them. The load generator (openloop.py) runs in a child process
that never imports JAX, so client and server do not share one interpreter
lock; it warms up at the cell's own rate, then sends the window's
requests on their schedule.

Host-clock records: each GET's latency from its due time to its last body
byte, and how late the generator sent it. Checked, after the window:
every response body against the payload made from the seed.

Traffic keys: "rate" (GETs/s), "clients", "degraded" (bool),
"warmup_seconds", "trace_seconds".
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

import cluster
import dataset
import volbuild

VID = 1
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ask(proc, key: str, timeout: float) -> dict:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        msg = json.loads(line)
        if key in msg:
            return msg
    raise RuntimeError(f"load generator gave no {key!r} "
                       f"(exit {proc.poll()})")


def _warm_reconstruct(c) -> dict:
    """Compile (or load from the cache) the reconstruct program of every
    batch width the dispatch scheduler can form, up to its slab cap, for
    each survivor set the degraded GETs read. The server compiles one
    program per width (n blocks side by side), and a freeze of the
    machine lets many degraded GETs meet in one batch: warmed only by
    GETs, the window met widths the warm-up had not formed. Zero blocks
    go straight to the scheduler, around the reconstructed-block cache,
    with the arguments of the server's degraded read (all missing rows,
    `want` None under RS). Returns {survivor set: widths formed}."""
    import numpy as np
    from seaweedfs_tpu.ops import dispatch

    srv = c.volume
    ev = next(loc.ec_volumes[VID] for loc in srv.store.locations
              if VID in loc.ec_volumes)
    sched = dispatch.scheduler_for(ev.coder)
    geom = ev.geo.code_geometry()
    have = tuple(sorted(ev.shard_files))
    lost = [i for i in range(ev.geo.data_shards) if i not in have]
    sets = sorted({tuple(geom.repair_plan((sid,), have).reads)
                   for sid in lost})
    block = srv.ec_recon_cache.block_size
    formed = {}
    for pres in sets:
        zero = np.zeros((len(pres), block), np.uint8)
        got = set()
        for n in range(1, sched.max_slabs + 1):
            futs = [sched.reconstruct_stacked(pres, zero)
                    for _ in range(n)]
            for f in futs:
                f.result()
                got.add(f.batch_slabs)
        formed[str(list(pres))] = sorted(got)
    return formed


def setup(run) -> None:
    from seaweedfs_tpu.pb import volume_server_pb2 as vs

    cfg, tr = run.config, run.traffic
    t0 = time.perf_counter()
    objs = dataset.objects(cfg, run.seed, 0)
    dat = volbuild.write_volume(
        os.path.join(run.workdir, str(VID)), objs,
        mime=cfg.get("mime", "").encode(),
        last_modified=cfg["last_modified"],
        append_at_ns=cfg["last_modified"] * 10**9)
    n_obj = len(objs.ids)
    del objs
    phases = {"volume built": time.perf_counter() - t0}
    run.say(f"volume: {n_obj} objects, .dat {dat} bytes, built from the "
            f"seed in {phases['volume built']:.2f} s")
    t0 = time.perf_counter()
    c = cluster.start(run.workdir, max_volumes=1)
    run.state["cluster"] = c
    k, m = cfg["data_shards"], cfg["parity_shards"]
    c.stub.VolumeMarkReadonly(vs.VolumeMarkReadonlyRequest(volume_id=VID),
                              timeout=60)
    phases["servers started"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    c.stub.VolumeEcShardsGenerate(
        vs.VolumeEcShardsGenerateRequest(volume_id=VID), timeout=600)
    c.stub.VolumeUnmount(vs.VolumeUnmountRequest(volume_id=VID), timeout=60)
    shards = list(range(k + m))
    if tr["degraded"]:
        lost = list(cfg["lost_shards"])
        c.stub.VolumeEcShardsDelete(vs.VolumeEcShardsDeleteRequest(
            volume_id=VID, shard_ids=lost), timeout=60)
        shards = [i for i in shards if i not in lost]
    c.stub.VolumeEcShardsMount(vs.VolumeEcShardsMountRequest(
        volume_id=VID, shard_ids=shards), timeout=60)
    phases["encoded and mounted"] = time.perf_counter() - t0
    run.say(f"served from shards {shards}")
    if tr["degraded"]:
        t0 = time.perf_counter()
        widths = _warm_reconstruct(c)
        phases["reconstruct programs"] = time.perf_counter() - t0
        run.say(f"reconstruct batches warmed: {widths}")

    results = os.path.join(run.workdir, "openloop.json")
    seconds = tr["trace_seconds"] if run.trace else run.seconds
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "openloop.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=HERE)
    run.state.update(proc=proc, results=results, seconds=seconds)
    proc.stdin.write(json.dumps({
        "address": c.public, "vid": VID, "config": cfg, "seed": run.seed,
        "rate": tr["rate"], "clients": tr["clients"], "seconds": seconds,
        "warmup_seconds": tr["warmup_seconds"], "results": results,
    }) + "\n")
    proc.stdin.flush()
    ready = _ask(proc, "ready", 300 + tr["warmup_seconds"])
    phases.update(ready.get("phases", {}))
    run.host.setdefault("lines", []).append(
        "set-up phases, s: " + json.dumps(
            {k: round(v, 3) for k, v in phases.items()}))


def window(run) -> None:
    proc = run.state["proc"]
    with run.annotate("get"):
        proc.stdin.write("go\n")
        proc.stdin.flush()
        # answers may come up to a minute past the close, then the child
        # compares every body
        _ask(proc, "done", run.state["seconds"] + 240)


def teardown(run) -> None:
    st = run.state
    proc = st.get("proc")
    if proc is not None:
        try:
            proc.stdin.close()
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    if "cluster" in st:
        st["cluster"].stop()
    lines = run.host.setdefault("lines", [])
    if proc is None or not os.path.exists(st["results"]):
        return
    with open(st["results"]) as f:
        res = json.load(f)
    # a failed, wrong or unanswered GET counts as slower than every other:
    # it waited at least the window and the minute past it
    cap = (st["seconds"] + 60) * 1e3
    lat = [x if x is not None else cap for x in res["latency_ms"]]
    run.host.update(latency_ms=lat, openloop=res)
    run.attempted = res["scheduled"]
    run.failed = res["wrong"]
    late = res["late_ms"]
    q = sorted(lat)
    pct = {p: q[max(0, math.ceil(p / 100 * len(q)) - 1)]
           for p in (50, 90, 99, 99.9)}
    lines.append("latency from due time, ms: " + ", ".join(
        f"p{p:g} {v:.3f}" for p, v in pct.items()) + f", max {q[-1]:.3f}")
    lines.append(f"generator: {res['scheduled']} GETs due in "
                 f"{st['seconds']} s; sent late by p50 {late['p50']:.3f} "
                 f"ms, p99 {late['p99']:.3f} ms, max {late['max']:.3f} ms;"
                 f" status {json.dumps(res['status'])}")


def check(run) -> None:
    res = run.host.get("openloop")
    if res is None:
        run.check("get_wrong", 1, 0)
        return
    run.check("get_wrong", res["wrong"], 0)
