"""End-to-end request tracing (ISSUE 7): span propagation across
S3 → filer → volume HTTP/gRPC → EC dispatch, W3C traceparent parsing
(hostile headers re-root, never 500), tail-based retention, the
`trace.dump` shell command, and the dispatch-attribution attributes
(queue wait, batch factor, chip) on a degraded read under 4-shard loss.
"""

from __future__ import annotations

import glob
import io
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
import requests

from seaweedfs_tpu.operation import submit
from seaweedfs_tpu.pb import rpc
from seaweedfs_tpu.pb import volume_server_pb2 as vs
from seaweedfs_tpu.s3api.server import S3Server
from seaweedfs_tpu.server.filer import FilerServer
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.shell.commands.trace_cmd import gather_trace
from seaweedfs_tpu.shell.env import CommandEnv
from seaweedfs_tpu.shell.registry import run_command
from seaweedfs_tpu.storage.ec_locate import Geometry
from seaweedfs_tpu.storage.file_id import parse_file_id
from seaweedfs_tpu.utils import failpoint, trace

TEST_GEO = Geometry(large_block=10000, small_block=100)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# -- traceparent parsing ----------------------------------------------------

def test_parse_traceparent_valid():
    tid = "a" * 32
    sid = "b" * 16
    assert trace.parse_traceparent(f"00-{tid}-{sid}-01") == (tid, sid,
                                                             True)
    assert trace.parse_traceparent(f"00-{tid}-{sid}-00") == (tid, sid,
                                                             False)
    # future version with extra fields still parses the leading four
    assert trace.parse_traceparent(f"cc-{tid}-{sid}-01-extra") == (
        tid, sid, True)


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00", "00-short-b-01",
    "00-" + "z" * 32 + "-" + "b" * 16 + "-01",     # non-hex trace id
    "00-" + "0" * 32 + "-" + "b" * 16 + "-01",     # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",     # all-zero span id
    "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",     # forbidden version
    "00-" + "a" * 31 + "-" + "b" * 16 + "-01",     # wrong length
    "00-" + "a" * 32 + "-" + "b" * 16 + "-zz",     # bad flags
    12345, b"00-aa-bb-01",
])
def test_parse_traceparent_malformed(bad):
    assert trace.parse_traceparent(bad) is None


# -- span mechanics ---------------------------------------------------------

def test_span_nesting_and_store():
    with trace.span("root") as root:
        assert trace.current() is root
        with trace.span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        assert trace.current() is root
    assert trace.current() is None
    spans = trace.STORE.trace(root.trace_id)
    assert {s["name"] for s in spans} == {"root", "child"}


def test_child_only_without_parent_records_nothing():
    before = trace.STORE.recorded
    with trace.span("lonely", child_only=True) as sp:
        sp.set_attr(x=1)  # absorbing no-op
    assert trace.STORE.recorded == before
    assert sp.traceparent() == ""


def test_disabled_plane_is_noop(monkeypatch):
    monkeypatch.setenv("SWFS_TRACE", "0")
    trace.refresh_config()  # the env knob is TTL-cached on the hot path
    try:
        before = trace.STORE.recorded
        with trace.span("off") as sp:
            assert sp is trace.NOOP
            assert trace.traceparent() == ""
        assert trace.STORE.recorded == before
    finally:
        monkeypatch.undo()
        trace.refresh_config()


def test_retention_pins_error_and_slow(monkeypatch):
    monkeypatch.setenv("SWFS_TRACE_SLOW_MS", "10")
    trace.refresh_config()
    try:
        with trace.span("fast-ok"):
            pass
        with trace.span("slow-one") as slow:
            time.sleep(0.02)
        with pytest.raises(RuntimeError):
            with trace.span("err-one") as err:
                raise RuntimeError("boom")
        retained = {s["traceId"]
                    for s in trace.STORE.retained_summaries()}
        assert slow.trace_id in retained
        assert err.trace_id in retained
        err_spans = trace.STORE.trace(err.trace_id)
        assert any("boom" in s["error"] for s in err_spans)
    finally:
        monkeypatch.undo()
        trace.refresh_config()


def test_carrier_roundtrip_headers_and_grpc_metadata():
    with trace.span("origin") as sp:
        headers = trace.inject_headers({"X-Other": "1"})
        assert trace.parse_traceparent(headers["traceparent"])[0] == \
            sp.trace_id
    # HTTP-headers style carrier
    with trace.span("server-side", carrier=headers) as child:
        assert child.trace_id == sp.trace_id
    # gRPC invocation-metadata style carrier (list of pairs)
    md = [("user-agent", "x"), ("traceparent", sp.traceparent())]
    assert trace.carrier_has_context(md)
    with trace.span("grpc-side", carrier=md) as child2:
        assert child2.trace_id == sp.trace_id
    assert not trace.carrier_has_context([("user-agent", "x")])


def test_malformed_carrier_reroots():
    with trace.span("rerooted",
                    carrier={"traceparent": "not-a-traceparent"}) as sp:
        assert len(sp.trace_id) == 32  # fresh root, not a crash


def test_histogram_exemplars_link_to_traces():
    from seaweedfs_tpu.utils import stats

    h = stats.Histogram("SeaweedFS_test_exemplar_seconds", "test only")
    try:
        with trace.span("exemplar-src") as sp:
            h.observe(0.05, type="t")
        ex = h.exemplars(type="t")
        assert any(v["traceId"] == sp.trace_id for v in ex.values())
        with_ex = h.render(exemplars=True)
        assert f'trace_id="{sp.trace_id}"' in with_ex
        assert " # {" not in h.render()  # plain 0.0.4 stays clean
    finally:
        with stats._REG_MU:
            stats._REGISTRY.remove(h)


# -- one clock with the device: spans in the profiler's trace ---------------

def _host_events(trace_dir, prefix):
    """{name: [(host line index, duration ms)]} of the host-plane events
    whose names start with `prefix` in the capture under trace_dir."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    out.setdefault(e.name, []).append(
                        (li, e.duration_ns / 1e6))
    return out


def test_span_writes_itself_into_a_profiler_capture(tmp_path):
    """While a capture runs, a recorded span is a host-plane event of its
    own name, on the line of the thread that ran it, as long as the
    span's own reading."""
    import jax

    assert trace._profiler_annotation("test.idle") is None  # no capture
    done = {}

    def worker():
        with trace.span("test.xplane.worker") as sp:
            with jax.profiler.TraceAnnotation("test.xplane.marker"):
                time.sleep(0.02)
        done["worker"] = sp

    jax.profiler.start_trace(str(tmp_path))
    try:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        with trace.span("test.xplane.main") as main_sp:
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path, "test.xplane.")
    (w_line, w_ms), = ev["test.xplane.worker"]
    (marker_line, _), = ev["test.xplane.marker"]
    (main_line, main_ms), = ev["test.xplane.main"]
    assert w_line == marker_line != main_line
    assert abs(w_ms - done["worker"].duration_ms) < 1.0
    assert abs(main_ms - main_sp.duration_ms) < 1.0


def test_disabled_plane_writes_nothing_into_a_capture(tmp_path,
                                                      monkeypatch):
    import jax

    monkeypatch.setenv("SWFS_TRACE", "0")
    trace.refresh_config()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace.span("test.off.span"):
                time.sleep(0.002)
            with trace.timed("test.off.timed") as t:
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
    finally:
        monkeypatch.undo()
        trace.refresh_config()
    assert t.seconds >= 0.002  # measured all the same
    assert _host_events(tmp_path, "test.off.") == {}


def test_importing_trace_does_not_import_jax():
    code = ("import sys, seaweedfs_tpu.utils.trace as t\n"
            "with t.span('x'):\n    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_timed_feeds_one_reading_to_span_and_observer():
    seen = []
    with trace.span("timed-root") as root:
        with trace.timed("timed-child", observe=seen.append) as t:
            time.sleep(0.003)
    child, = [s for s in trace.STORE.trace(root.trace_id)
              if s["name"] == "timed-child"]
    assert seen == [t.seconds] and t.seconds >= 0.003
    assert child["durationMs"] == pytest.approx(t.seconds * 1e3, abs=1e-3)
    # child_only outside any span: nothing recorded, still measured
    before = trace.STORE.recorded
    with trace.timed("timed-lonely", child_only=True,
                     observe=seen.append) as t2:
        pass
    assert trace.STORE.recorded == before and seen[-1] == t2.seconds


def _ring_spans(trace_id: str) -> list:
    """The Span objects of one trace still in the store's ring."""
    with trace.STORE._lock:
        return [s for s in trace.STORE._ring if s.trace_id == trace_id]


def test_dispatch_lock_wait_span_once_per_flush_on_the_flushing_thread(
        tmp_path):
    """Each flush records one `ec.dispatch.lock_wait` span on the thread
    that flushes, closed before that flush's `ec.dispatch.encode` span
    opens: a consumer's demand flush on the consumer's thread (a child
    of its span), a window flush on the flusher thread (a child of the
    first slab's span). `SeaweedFS_ec_dispatch_lock_wait_seconds` grows
    by the spans' readings (other schedulers of the process may add to
    it)."""
    import jax

    from seaweedfs_tpu.models.coder import new_coder
    from seaweedfs_tpu.ops import dispatch
    from seaweedfs_tpu.utils import stats

    coder = new_coder(10, 4, "cpu")
    rng = np.random.default_rng(5)

    def slab():
        return rng.integers(0, 256, (10, 64), dtype=np.uint8)

    waited0 = stats.EC_DISPATCH_LOCK_WAIT.value(lane="encode", chip="-")
    demand = dispatch.EcDispatchScheduler(coder, window=60.0)
    windowed = dispatch.EcDispatchScheduler(coder, window=0.005)
    roots = {}

    def consumer():
        with trace.span("test.lock.consumer") as sp:
            futs = [demand.encode_parity(slab()) for _ in range(3)]
            np.asarray(futs[0])  # demand flush, on this thread
        roots["consumer"] = sp

    jax.profiler.start_trace(str(tmp_path))
    try:
        t = threading.Thread(target=consumer)
        t.start()
        t.join()
        with trace.span("test.lock.main") as sp:
            fut = windowed.encode_parity(slab())
            for _ in range(1000):
                if fut.done():
                    break
                time.sleep(0.01)
            assert fut.done()  # the flusher's window flush, no demand
        roots["main"] = sp
    finally:
        jax.profiler.stop_trace()
        demand.close()
        windowed.close()

    waited = 0.0
    for root in roots.values():
        spans = _ring_spans(root.trace_id)
        lock, = [s for s in spans if s.name == "ec.dispatch.lock_wait"]
        batch, = [s for s in spans if s.name == "ec.dispatch.encode"]
        assert lock.parent_id == batch.parent_id == root.span_id
        assert lock.attrs == {"lane": "encode", "chip": "-"}
        assert lock._t0 + lock.duration_ms / 1e3 <= batch._t0
        waited += lock.duration_ms / 1e3
    assert stats.EC_DISPATCH_LOCK_WAIT.value(
        lane="encode", chip="-") - waited0 >= waited - 1e-9 > 0
    ev = _host_events(tmp_path, "test.lock.")
    ev.update(_host_events(tmp_path, "ec.dispatch."))
    (consumer_line, _), = ev["test.lock.consumer"]
    (main_line, _), = ev["test.lock.main"]
    lock_lines = sorted(li for li, _ in ev["ec.dispatch.lock_wait"])
    assert lock_lines == sorted(li for li, _ in ev["ec.dispatch.encode"])
    assert len(lock_lines) == 2 and consumer_line in lock_lines
    assert main_line not in lock_lines  # the flusher thread's line


def _encode_and_rebuild(tmp_path, lost: int = 7) -> dict:
    """A 1,234-byte volume encoded and one shard rebuilt, each under a
    span of its own, with four read streams a slab: {op: root span}."""
    from seaweedfs_tpu.models.coder import new_coder
    from seaweedfs_tpu.storage import ec_files

    base = str(tmp_path / "q")
    rng = np.random.default_rng(23)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 1234, dtype=np.uint8).tobytes())
    coder = new_coder(10, 4, "cpu")
    roots = {}
    with trace.span("test.queue.encode") as roots["encode"]:
        ec_files.generate_ec_files(base, coder, TEST_GEO, batch_size=50)
    os.remove(TEST_GEO.shard_file_name(base, lost))
    with trace.span("test.queue.rebuild") as roots["rebuild"]:
        ec_files.rebuild_ec_files(base, coder, TEST_GEO, batch_size=50)
    return roots


def test_read_queue_span_once_per_slab(tmp_path, monkeypatch):
    """Every slab filled on the `ec_read` pool records one
    `ec.<op>.read_queue` span, a child of its `ec.<op>.read` span and
    inside it, for the encode and the rebuild; the counter
    `SeaweedFS_ec_pipeline_read_queue_seconds{op}` grows by their
    readings (other pipelines of the process may add to it)."""
    from seaweedfs_tpu.utils import stats

    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    queued0 = {op: stats.EC_PIPELINE_READ_QUEUE.value(op=op)
               for op in ("encode", "rebuild")}
    slabs0 = {op: stats.EC_PIPELINE_SLABS.value(op=op)
              for op in ("encode", "rebuild")}
    for op, root in _encode_and_rebuild(tmp_path).items():
        spans = _ring_spans(root.trace_id)
        reads = {s.span_id: s for s in spans if s.name == f"ec.{op}.read"}
        queues = [s for s in spans if s.name == f"ec.{op}.read_queue"]
        slabs = stats.EC_PIPELINE_SLABS.value(op=op) - slabs0[op]
        assert slabs >= 2 and len(reads) == len(queues) == slabs
        assert sorted(q.parent_id for q in queues) == sorted(reads)
        for q in queues:
            read = reads[q.parent_id]
            assert read._t0 <= q._t0
            assert q._t0 + q.duration_ms / 1e3 <= \
                read._t0 + read.duration_ms / 1e3
        assert stats.EC_PIPELINE_READ_QUEUE.value(op=op) - queued0[op] \
            >= sum(q.duration_ms for q in queues) / 1e3 - 1e-9


def test_buffer_wait_span_once_per_slab(tmp_path, monkeypatch):
    """The reader thread's wait for each slab's recycled buffer is one
    `ec.<op>.buffer_wait` span, a child of the pipeline's root span and
    closed before the slab's `ec.<op>.read` opens, for the encode and
    the rebuild; `SeaweedFS_ec_pipeline_buffer_wait_seconds{op}` grows
    by their readings (other pipelines of the process may add to it)."""
    from seaweedfs_tpu.utils import stats

    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    waited0 = {op: stats.EC_PIPELINE_BUFFER_WAIT.value(op=op)
               for op in ("encode", "rebuild")}
    slabs0 = {op: stats.EC_PIPELINE_SLABS.value(op=op)
              for op in ("encode", "rebuild")}
    for op, root in _encode_and_rebuild(tmp_path).items():
        spans = _ring_spans(root.trace_id)
        reads = sorted((s for s in spans if s.name == f"ec.{op}.read"),
                       key=lambda s: s._t0)
        waits = sorted((s for s in spans
                        if s.name == f"ec.{op}.buffer_wait"),
                       key=lambda s: s._t0)
        slabs = stats.EC_PIPELINE_SLABS.value(op=op) - slabs0[op]
        assert slabs >= 2 and len(reads) == len(waits) == slabs
        assert {w.parent_id for w in waits} == {root.span_id}
        for wait, read in zip(waits, reads):
            assert wait._t0 + wait.duration_ms / 1e3 <= read._t0
        assert stats.EC_PIPELINE_BUFFER_WAIT.value(op=op) - waited0[op] \
            >= sum(w.duration_ms for w in waits) / 1e3 - 1e-9


def test_disabled_plane_records_no_lock_wait_or_read_queue(tmp_path,
                                                           monkeypatch):
    """With SWFS_TRACE=0 the lock wait and the read queue write nothing,
    neither to the store nor into a capture; their counters are fed all
    the same."""
    import jax

    from seaweedfs_tpu.utils import stats

    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    monkeypatch.setenv("SWFS_TRACE", "0")
    trace.refresh_config()
    waited0 = stats.EC_DISPATCH_LOCK_WAIT.value(lane="encode")
    queued0 = stats.EC_PIPELINE_READ_QUEUE.value(op="encode")
    recorded0 = trace.STORE.recorded
    try:
        jax.profiler.start_trace(str(tmp_path / "capture"))
        try:
            _encode_and_rebuild(tmp_path)
        finally:
            jax.profiler.stop_trace()
    finally:
        monkeypatch.undo()
        trace.refresh_config()
    assert trace.STORE.recorded == recorded0
    assert _host_events(tmp_path / "capture", "ec.") == {}
    assert stats.EC_DISPATCH_LOCK_WAIT.value(lane="encode") > waited0
    assert stats.EC_PIPELINE_READ_QUEUE.value(op="encode") > queued0


# -- live cluster: propagation, degraded read, trace.dump, fuzz ------------

@pytest.fixture(scope="module")
def trace_stack(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("trace")
    mport = _free_port()
    master = MasterServer(ip="localhost", port=mport,
                          volume_size_limit_mb=64)
    master.start(vacuum_interval=3600)
    volumes = []
    for i in range(2):
        v = VolumeServer(directories=[str(tmp / f"vol{i}")],
                         master=f"localhost:{mport}", ip="localhost",
                         port=_free_port(), pulse_seconds=1,
                         ec_geometry=TEST_GEO)
        v.start()
        volumes.append(v)
    fsrv = FilerServer(ip="localhost", port=_free_port(),
                       master=f"localhost:{mport}",
                       store_dir=str(tmp / "filer"),
                       chunk_size=32 * 1024)
    fsrv.start()
    s3 = S3Server(port=_free_port(), filer=fsrv.address)
    s3.start()
    deadline = time.time() + 10
    while time.time() < deadline and len(master.topo.nodes) < 2:
        time.sleep(0.05)
    assert len(master.topo.nodes) == 2
    yield master, volumes, fsrv, s3
    s3.stop()
    fsrv.stop()
    for v in volumes:
        v.stop()
    master.stop()
    rpc.reset_channels()


def _wait(cond, timeout=15.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture(scope="module")
def ec_object(trace_stack):
    """An S3 object on an EC volume whose shards split across both
    servers: 0..6 on the source, 7..13 on the other. -> (body, src, dst)"""
    master, volumes, fsrv, s3 = trace_stack
    rng = np.random.default_rng(7)
    body = rng.integers(0, 256, size=3000, dtype=np.uint8).tobytes()
    requests.put(f"http://localhost:{s3.port}/tracebkt", timeout=10)
    r = requests.put(f"http://localhost:{s3.port}/tracebkt/obj.bin",
                     data=body, timeout=30)
    assert r.status_code == 200, r.text
    # the chunk fid names the volume to convert
    entry = fsrv.filer.find_entry("/buckets/tracebkt/obj.bin")
    vid = parse_file_id(entry.chunks[0].file_id).volume_id
    src = next(v for v in volumes if v.store.has_volume(vid))
    dst = next(v for v in volumes if v is not src)
    stub_src = rpc.volume_stub(rpc.grpc_address(src.address))
    stub_dst = rpc.volume_stub(rpc.grpc_address(dst.address))
    stub_src.VolumeMarkReadonly(
        vs.VolumeMarkReadonlyRequest(volume_id=vid), timeout=30)
    stub_src.VolumeEcShardsGenerate(
        vs.VolumeEcShardsGenerateRequest(volume_id=vid), timeout=120)
    # move shards 7..13 to the second server so any reconstruct must
    # gather survivors over gRPC
    moved = list(range(7, 14))
    stub_dst.VolumeEcShardsCopy(
        vs.VolumeEcShardsCopyRequest(
            volume_id=vid, shard_ids=moved, copy_ecx_file=True,
            copy_vif_file=True, source_data_node=src.address),
        timeout=120)
    stub_src.VolumeUnmount(vs.VolumeUnmountRequest(volume_id=vid),
                           timeout=30)
    stub_src.VolumeEcShardsDelete(
        vs.VolumeEcShardsDeleteRequest(volume_id=vid, shard_ids=moved),
        timeout=30)
    stub_src.VolumeEcShardsMount(
        vs.VolumeEcShardsMountRequest(volume_id=vid,
                                      shard_ids=list(range(7))),
        timeout=30)
    stub_dst.VolumeEcShardsMount(
        vs.VolumeEcShardsMountRequest(volume_id=vid, shard_ids=moved),
        timeout=30)
    _wait(lambda: len(master.topo.lookup_ec_shards(vid) or {}) == 14,
          msg="all 14 shards registered")
    return body, src, dst


def _degraded_s3_get(fsrv, s3, body):
    """GET the EC'd object with shards 0..3 unreadable; -> its trace id."""
    saved_cache = fsrv.chunk_cache
    fsrv.chunk_cache = None
    lost = "|".join(f"shard={i}," for i in range(4))
    try:
        with failpoint.active("ec.shard.read", p=1.0, match=lost) as fp:
            got = requests.get(
                f"http://localhost:{s3.port}/tracebkt/obj.bin",
                timeout=60)
            assert got.status_code == 200
            assert got.content == body
            assert fp.hits > 0, "shard loss never injected"
    finally:
        fsrv.chunk_cache = saved_cache
    trace_id = got.headers.get("X-Trace-Id", "")
    assert len(trace_id) == 32, got.headers
    return trace_id


def test_degraded_s3_read_produces_cross_server_trace(trace_stack,
                                                      ec_object):
    """The acceptance path: one S3 GET of an EC'd object under 4-shard
    loss returns an X-Trace-Id whose trace — gathered by `trace.dump`
    from every server — covers s3 ingress → filer ladder → volume →
    remote shard gRPC → dispatch-batched reconstruct, with queue-wait,
    batch-factor and chip attributes present, and spans from the filer
    plus BOTH volume servers."""
    master, volumes, fsrv, s3 = trace_stack
    body, src, dst = ec_object

    # the filer chunk cache was write-through-populated at PUT; the
    # degraded read must hit the volume plane, where the loss lives
    trace_id = _degraded_s3_get(fsrv, s3, body)

    # --- trace.dump gathers the trace from every server it touched
    env = CommandEnv(master.address, filer=fsrv.address)
    spans, targets = gather_trace(env, trace_id,
                                  extra=[f"localhost:{s3.port}"])
    assert len(targets) >= 4  # master + 2 volume servers + filer + s3
    names = {s["name"] for s in spans}
    assert "s3.request" in names
    assert "filer.read" in names
    assert "filer.chunk_read" in names
    assert "volume.read" in names or "grpc.VolumeEcShardRead" in names
    assert "volume.ec.reconstruct" in names
    # acceptance: spans from >= 3 servers incl. the filer and BOTH
    # volume servers (the reconstruct gathered survivors over gRPC)
    servers = {s["server"] for s in spans if s["server"]}
    assert fsrv.address in servers
    assert {src.address, dst.address} <= servers, servers
    assert len(servers) >= 3
    # dispatch attribution on the reconstruct span(s)
    recon = [s for s in spans if s["name"] == "volume.ec.reconstruct"
             and "dispatchBatchSlabs" in s["attrs"]]
    assert recon, "no reconstruct span carried dispatch attribution"
    a = recon[0]["attrs"]
    assert a["dispatchBatchSlabs"] >= 1
    assert a["dispatchQueueWaitMs"] >= 0
    assert "dispatchChip" in a
    assert a["survivors"] >= 10
    # every span of the tree shares the one trace id
    assert {s["traceId"] for s in spans} == {trace_id}

    # --- the shell command renders it
    out = io.StringIO()
    assert run_command(env, f"trace.dump -trace={trace_id} "
                            f"-server=localhost:{s3.port}", out=out) == 0
    text = out.getvalue()
    assert trace_id in text
    assert "s3.request" in text and "volume.ec.reconstruct" in text

    # cache hit/miss attribution: with the cache back on, a re-read
    # marks its chunk-read span as a hit
    got2 = requests.get(f"http://localhost:{s3.port}/tracebkt/obj.bin",
                        timeout=30)
    tid2 = got2.headers["X-Trace-Id"]
    spans2 = trace.STORE.trace(tid2)
    reads = [s for s in spans2 if s["name"] == "filer.chunk_read"]
    assert reads and all(s["attrs"].get("cache") in ("hit", "miss")
                         for s in reads)


def _tree(spans):
    by_id = {s["spanId"]: s for s in spans}
    return lambda s: by_id.get(s["parentId"], {}).get("name")


def test_degraded_read_splits_reconstruct_into_gather_and_wait(
        trace_stack, ec_object):
    """Under volume.ec.reconstruct, the survivor gather (local preads and
    remote fetches) and the wait on the dispatch scheduler are spans of
    their own; the batch that ran the slab is a span under the wait."""
    master, volumes, fsrv, s3 = trace_stack
    body, src, dst = ec_object
    trace_id = _degraded_s3_get(fsrv, s3, body)
    spans = trace.STORE.trace(trace_id)
    parent = _tree(spans)
    recon = [s for s in spans if s["name"] == "volume.ec.reconstruct"]
    assert recon
    gathers = [s for s in spans if s["name"] == "volume.ec.survivor_read"]
    waits = [s for s in spans if s["name"] == "ec.dispatch.wait"]
    assert len(waits) == len(recon)
    # each reconstruct gathers once, or twice where a planned survivor
    # fails and the any-k backstop gathers again
    assert len(recon) <= len(gathers) <= 2 * len(recon)
    assert {parent(s) for s in gathers + waits} == {"volume.ec.reconstruct"}
    done = [g for g in gathers if "survivors" in g["attrs"]]
    assert len(done) == len(recon)
    assert all(g["attrs"]["survivors"] >= 10 for g in done)
    batches = [s for s in spans if s["name"] == "ec.dispatch.reconstruct"]
    assert batches and {parent(s) for s in batches} == {"ec.dispatch.wait"}
    assert all(b["attrs"]["slabs"] >= 1 for b in batches)
    # the wait covers the batch: submit until the rows are host bytes
    assert max(w["durationMs"] for w in waits) >= \
        min(b["durationMs"] for b in batches)
    assert not any("dispatchWallMs" in s["attrs"] for s in spans)


def test_ec_encode_and_rebuild_spans_and_pipeline_counters(trace_stack):
    """One span per slab and phase under volume.ec.encode and
    volume.ec.rebuild, as many slabs as the pipeline counted, and the
    SeaweedFS_ec_pipeline_* counters grown by the same count."""
    from seaweedfs_tpu.utils import stats

    master, volumes, fsrv, s3 = trace_stack
    rng = np.random.default_rng(11)
    fids = [submit(master.address, rng.integers(
        0, 256, size=4000, dtype=np.uint8).tobytes(),
        collection="spanpipe")["fid"] for _ in range(4)]
    vid = parse_file_id(fids[0]).volume_id
    srv = next(v for v in volumes if v.store.has_volume(vid))
    stub = rpc.volume_stub(rpc.grpc_address(srv.address))
    stub.VolumeMarkReadonly(vs.VolumeMarkReadonlyRequest(volume_id=vid),
                            timeout=30)

    def counted():
        return {(op, ph): stats.EC_PIPELINE_SECONDS.value(op=op, phase=ph)
                for op in ("encode", "rebuild")
                for ph in ("read", "submit", "device_wait", "write")}, {
            op: stats.EC_PIPELINE_SLABS.value(op=op)
            for op in ("encode", "rebuild")}

    def root_and_children(name):
        with trace.STORE._lock:
            roots = [s for s in trace.STORE._ring if s.name == name
                     and s.attrs.get("vid") == vid]
        root, = roots
        spans = trace.STORE.trace(root.trace_id)
        kids = [s for s in spans if s["parentId"] == root.span_id]
        by_name: dict = {}
        for s in kids:
            by_name[s["name"]] = by_name.get(s["name"], 0) + 1
        return root, by_name

    sec0, slabs0 = counted()
    stub.VolumeEcShardsGenerate(vs.VolumeEcShardsGenerateRequest(
        volume_id=vid, collection="spanpipe"), timeout=120)
    sec1, slabs1 = counted()
    root, kids = root_and_children("volume.ec.encode")
    n = root.attrs["slabs"]
    assert n > 1 and root.parent_id == ""  # no traceparent: a new root
    assert slabs1["encode"] - slabs0["encode"] == n
    assert kids["ec.encode.read"] == kids["ec.encode.submit"] == n
    assert kids["ec.encode.device_wait"] == n
    assert kids["ec.encode.write"] == 14 * n
    assert all(sec1["encode", ph] > sec0["encode", ph]
               for ph in ("read", "submit", "device_wait", "write"))

    lost = [0, 5, 13]
    stub.VolumeEcShardsDelete(vs.VolumeEcShardsDeleteRequest(
        volume_id=vid, collection="spanpipe", shard_ids=lost), timeout=30)
    resp = stub.VolumeEcShardsRebuild(vs.VolumeEcShardsRebuildRequest(
        volume_id=vid, collection="spanpipe"), timeout=120)
    assert sorted(resp.rebuilt_shard_ids) == lost
    sec2, slabs2 = counted()
    root, kids = root_and_children("volume.ec.rebuild")
    n = root.attrs["slabs"]
    assert n >= 1 and slabs2["rebuild"] - slabs1["rebuild"] == n
    assert kids["ec.rebuild.read"] == kids["ec.rebuild.submit"] == n
    assert kids["ec.rebuild.device_wait"] == n
    assert kids["ec.rebuild.write"] == len(lost) * n
    assert all(sec2["rebuild", ph] > sec1["rebuild", ph]
               for ph in ("read", "submit", "device_wait", "write"))


def test_malformed_traceparent_never_500s_always_reroots(trace_stack):
    """Fuzz the ingress planes with hostile traceparent headers: no
    request may fail because of one, and each response must carry a
    FRESH trace id (re-rooted, not parroting garbage)."""
    master, volumes, fsrv, s3 = trace_stack
    hostile = [
        "garbage", "00", "00-xx-yy-zz", "\x00\x01binary",
        "00-" + "0" * 32 + "-" + "0" * 16 + "-01",
        "00-" + "f" * 400 + "-" + "b" * 16 + "-01",
        "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",
        "00-" + "a" * 32 + "-" + "b" * 16 + "-zz",
        "00-a-b-c-d-e-f-g", ",,,///---",
    ]
    targets = [
        f"http://localhost:{s3.port}/tracebkt/obj.bin",
        f"http://{fsrv.address}/buckets/tracebkt/obj.bin",
        f"http://{master.address}/dir/assign",
    ]
    for url in targets:
        for tp in hostile:
            try:
                r = requests.get(url, headers={"traceparent": tp},
                                 timeout=30)
            except requests.RequestException as e:
                raise AssertionError(f"{url} with {tp!r} broke the "
                                     f"connection: {e}")
            assert r.status_code < 500, (url, tp, r.status_code, r.text)
            tid = r.headers.get("X-Trace-Id", "")
            assert len(tid) == 32 and tid not in tp, (url, tp, tid)
    # a VALID traceparent, by contrast, is honored end to end
    good_tid = "c" * 32
    r = requests.get(targets[0],
                     headers={"traceparent":
                              f"00-{good_tid}-{'d' * 16}-01"},
                     timeout=30)
    assert r.status_code == 200
    assert r.headers["X-Trace-Id"] == good_tid
    assert trace.STORE.trace(good_tid), "propagated trace left no spans"


def test_grpc_metadata_propagation(trace_stack):
    """A gRPC call made inside a span carries the context as metadata;
    the servicer's handler span lands in the same trace with the
    server's address on it."""
    master, volumes, fsrv, s3 = trace_stack
    v = volumes[0]
    with trace.span("test.client") as sp:
        stub = rpc.volume_stub(rpc.grpc_address(v.address))
        stub.Ping(vs.PingRequest(), timeout=10)
    spans = trace.STORE.trace(sp.trace_id)
    grpc_spans = [s for s in spans if s["name"] == "grpc.Ping"]
    assert grpc_spans and grpc_spans[0]["server"] == v.address
    # background chatter without a span context creates NO grpc spans
    before = trace.STORE.recorded
    stub.Ping(vs.PingRequest(), timeout=10)
    with trace.STORE._lock:
        stray = [s for s in trace.STORE._ring
                 if s.name == "grpc.Ping" and s.trace_id != sp.trace_id]
    assert not stray
    assert trace.STORE.recorded == before


def test_retained_trace_span_cap(monkeypatch):
    """A client reusing ONE traceparent forever must not grow a pinned
    trace without bound (the 'all bounds are hard' contract)."""
    monkeypatch.setenv("SWFS_TRACE_SLOW_MS", "1")
    trace.refresh_config()
    try:
        tid = "e" * 32
        parent = (tid, "f" * 16, True)
        with trace.span("pin-me", parent=parent):
            time.sleep(0.005)  # slow -> pinned
        for _ in range(trace.RETAINED_TRACE_SPAN_CAP + 50):
            with trace.span("repeat", parent=parent):
                pass
        with trace.STORE._lock:
            held = len(trace.STORE._retained.get(tid, ()))
        assert held <= trace.RETAINED_TRACE_SPAN_CAP
    finally:
        monkeypatch.undo()
        trace.refresh_config()


def test_no_stale_trace_id_on_keepalive_connection(trace_stack):
    """A traced request followed by an untraced admin request on the
    SAME keep-alive connection must not leak the previous X-Trace-Id."""
    master, volumes, fsrv, s3 = trace_stack
    s = requests.Session()
    s.trust_env = False
    r1 = s.get(f"http://localhost:{s3.port}/tracebkt/obj.bin",
               timeout=30)
    assert r1.headers.get("X-Trace-Id")
    r2 = s.get(f"http://localhost:{s3.port}/status", timeout=30)
    assert "X-Trace-Id" not in r2.headers, r2.headers
    r3 = s.get(f"http://{fsrv.address}/status", timeout=30)
    assert "X-Trace-Id" not in r3.headers


def test_debug_traces_endpoints_and_status_trace_section(trace_stack):
    master, volumes, fsrv, s3 = trace_stack
    with trace.span("endpoint-probe") as sp:
        pass
    for addr in (master.address, volumes[0].address, fsrv.address,
                 f"localhost:{s3.port}"):
        r = requests.get(f"http://{addr}/debug/traces", timeout=10)
        assert r.status_code == 200
        payload = r.json()
        assert "retained" in payload and "store" in payload
        r = requests.get(f"http://{addr}/debug/traces",
                         params={"trace": sp.trace_id}, timeout=10)
        assert r.json()["traceId"] == sp.trace_id
        st = requests.get(f"http://{addr}/status", timeout=10).json()
        assert st["Trace"]["enabled"] is True


def test_submit_roundtrip_under_trace_has_assign_and_upload(trace_stack):
    """The client verbs attribute their own latency: a submit() inside
    a span yields client.assign + client.upload + master.grpc children."""
    master, volumes, fsrv, s3 = trace_stack
    with trace.span("client-verbs") as sp:
        res = submit(master.address, b"traced-bytes", filename="t.bin")
        assert "fid" in res, res
    # the server's volume.write span closes after its response is sent,
    # so it can land a moment after the client returns
    deadline = time.time() + 5
    while True:
        spans = trace.STORE.trace(sp.trace_id)
        names = {s["name"] for s in spans}
        if "volume.write" in names or time.time() > deadline:
            break
        time.sleep(0.01)
    assert "client.assign" in names
    assert "client.upload" in names
    assert "volume.write" in names  # the upload's server-side half
    # group-commit attribution rides the write span as attributes
    w = next(s for s in spans if s["name"] == "volume.write")
    assert w["attrs"].get("gcRole") in ("leader", "follower")
    assert w["attrs"].get("gcWaitMs", -1) >= 0
