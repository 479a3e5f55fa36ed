"""Streaming replica->EC conversion (ISSUE 6): pipelined archival encode.

Covers the tentpole end to end IN-PROCESS against a live 3-server
cluster — stream -> encode -> remote-write -> mount — plus the
satellites:

  * streamed-vs-local bit identity: golden `.ec00-.ec13` hashes through
    the streaming path (ragged tail + small/large block schedule
    boundaries), and the generate-then-copy path against the same golden
  * `crc32c_combine`-folded destination `.dig` digests equal to a
    full-file CRC re-read
  * `ec.stream.slab` failpoint (per-shard, per-slab-range matchable) +
    chaos: destination flap mid-stream resumes ONLY the missing range,
    final shards bit-identical, zero client-visible errors
  * the `_do_ec_encode` read-only rollback regression (generate failure
    must restore replica writability)
  * `SeaweedFS_ec_stream_*` metrics, the `/status` EcStream section and
    the VolumeEcShardsCopy fallback counters
"""

import hashlib
import io
import os
import socket
import time

import numpy as np
import pytest
import requests

from seaweedfs_tpu.operation import submit
from seaweedfs_tpu.pb import ec_stream_pb2 as es, rpc
from seaweedfs_tpu.pb import volume_server_pb2 as vs
from seaweedfs_tpu.scrub import digest as digest_mod
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.shell.env import CommandEnv
from seaweedfs_tpu.shell.registry import run_command
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.storage.crc import crc32c
from seaweedfs_tpu.storage.ec_locate import Geometry
from seaweedfs_tpu.storage.file_id import parse_file_id
from seaweedfs_tpu.utils import failpoint, stats

# small blocks so a few KB of needles cross the large/small row boundary
TEST_GEO = Geometry(large_block=10000, small_block=100)


def _free_port() -> int:
    """A free HTTP port whose +10000 gRPC sibling is also free."""
    for _ in range(50):
        with socket.socket() as s:
            s.bind(("", 0))
            port = s.getsockname()[1]
        if port + 10000 > 65535:
            continue
        with socket.socket() as s2:
            try:
                s2.bind(("", port + 10000))
            except OSError:
                continue
        return port
    raise RuntimeError("no free port pair found")


@pytest.fixture(autouse=True)
def _no_leaked_failpoints():
    failpoint.clear()
    yield
    failpoint.clear()


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """master + 3 volume servers; the python engine (native off) so the
    test controls the volume files directly."""
    old_native = os.environ.get("SEAWEEDFS_TPU_NATIVE")
    os.environ["SEAWEEDFS_TPU_NATIVE"] = "0"
    # wire chunks aligned to TEST_GEO.large_block so shard streams span
    # multiple chunks (the resume/failpoint tests target chunk offsets)
    old_chunk = os.environ.get("SWFS_EC_STREAM_CHUNK")
    os.environ["SWFS_EC_STREAM_CHUNK"] = str(TEST_GEO.large_block)
    tmp = tmp_path_factory.mktemp("ecstream")
    mport = _free_port()
    master = MasterServer(ip="localhost", port=mport,
                          volume_size_limit_mb=64)
    master.start(vacuum_interval=3600)
    volumes = []
    for i in range(3):
        vsrv = VolumeServer(
            directories=[str(tmp / f"vol{i}")],
            master=f"localhost:{mport}", ip="localhost",
            port=_free_port(), pulse_seconds=1, ec_geometry=TEST_GEO,
            # every test grows a fresh collection (~7 volumes each);
            # leave headroom so later tests never hit "no free slot"
            max_volume_counts=[120])
        vsrv.start()
        volumes.append(vsrv)
    deadline = time.time() + 10
    while time.time() < deadline and len(master.topo.nodes) < 3:
        time.sleep(0.05)
    assert len(master.topo.nodes) == 3, "volume servers did not register"
    env = CommandEnv(master.address)
    out = io.StringIO()
    assert run_command(env, "lock", out) == 0
    yield master, volumes, env
    for v in volumes:
        v.stop()
    master.stop()
    rpc.reset_channels()
    if old_native is None:
        os.environ.pop("SEAWEEDFS_TPU_NATIVE", None)
    else:
        os.environ["SEAWEEDFS_TPU_NATIVE"] = old_native
    if old_chunk is None:
        os.environ.pop("SWFS_EC_STREAM_CHUNK", None)
    else:
        os.environ["SWFS_EC_STREAM_CHUNK"] = old_chunk


def _make_volume(master, volumes, collection, n_needles=30, seed=0,
                 min_payload=0):
    """Write needles into ONE volume -> (vid, {fid: payload}, source
    server): the first needle goes through the live assign path to grow
    the collection, the rest PUT directly into that volume so the whole
    payload stripes one .dat. Sizes span sub-block to multi-block so the
    stripe crosses small/large rows with a ragged tail."""
    rng = np.random.default_rng(seed)
    res = submit(master.address, b"seed-needle", filename="seed.bin",
                 collection=collection)
    assert "fid" in res, res
    fid = res["fid"]
    vid = parse_file_id(fid).volume_id
    src = next(v for v in volumes if v.store.has_volume(vid))
    blobs = {fid: b"seed-needle"}
    # the master's sequencer adopts the max key it observes in
    # heartbeats, so a FIXED direct-key base would be chased and
    # eventually collided with by later seed assigns — descend the base
    # per test instead (seeds are distinct per collection)
    key = (0x7F - seed) << 24
    total = 0
    while len(blobs) < n_needles or total < min_payload:
        size = int(rng.integers(40, 5000))
        data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
        f = f"{vid},{key:x}00002026"
        r = requests.put(f"http://{src.address}/{f}", data=data,
                         timeout=30)
        assert r.status_code in (200, 201), r.text
        blobs[f] = data
        total += size
        key += 1
    return vid, blobs, src


def _snapshot_dat(src, vid, tmp_path) -> str:
    """Flush + copy the volume's .dat for offline golden encoding."""
    v = src.store.find_volume(vid)
    with v._lock:
        v._sync_buffers()
    base = str(tmp_path / f"golden{vid}")
    with open(v.file_name() + ".dat", "rb") as fin, \
            open(base + ".dat", "wb") as fout:
        fout.write(fin.read())
    return base


def _golden_hashes(base, geo) -> list[str]:
    from seaweedfs_tpu.models.coder import new_coder

    ec_files.generate_ec_files(base, new_coder(10, 4), geo)
    out = []
    for i in range(geo.total_shards):
        with open(geo.shard_file_name(base, i), "rb") as f:
            out.append(hashlib.sha256(f.read()).hexdigest())
    return out


def _cluster_shard_hashes(volumes, vid, geo, collection) -> dict[int, str]:
    """shard id -> sha256, gathered from whichever server holds it."""
    out = {}
    for srv in volumes:
        for loc in srv.store.locations:
            for sid in range(geo.total_shards):
                p = geo.shard_file_name(loc.base_name(collection, vid),
                                        sid)
                if os.path.exists(p):
                    with open(p, "rb") as f:
                        out[sid] = hashlib.sha256(f.read()).hexdigest()
    return out


def _encode(env, vid, extra="") -> str:
    out = io.StringIO()
    code = run_command(env, f"ec.encode -volumeId {vid} {extra}", out)
    assert code == 0, out.getvalue()
    return out.getvalue()


def _wait_ec_registered(master, vid, timeout=10):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if vid in master.topo.ec_shard_map and vid not in {
                v for n in master.topo.nodes.values() for v in n.volumes}:
            return
        time.sleep(0.1)
    raise AssertionError(f"ec volume {vid} never registered")


# -- tentpole: streamed bit identity + in-process smoke ---------------------

def test_streamed_encode_bit_identity_and_reads(cluster, tmp_path):
    """Tier-1 smoke of the full stream->encode->remote-write->mount path:
    streamed shards hash-identical to an offline golden encode of the
    same .dat, spread across remote servers, and every needle reads back
    over HTTP through the EC serving path."""
    master, volumes, env = cluster
    vid, blobs, src = _make_volume(master, volumes, "strm", seed=1)
    base = _snapshot_dat(src, vid, tmp_path)
    golden = _golden_hashes(base, TEST_GEO)

    msg = _encode(env, vid, "-stream 1")
    assert "streamed" in msg and "overlap ratio" in msg, msg
    _wait_ec_registered(master, vid)

    got = _cluster_shard_hashes(volumes, vid, TEST_GEO, "strm")
    assert len(got) == TEST_GEO.total_shards, sorted(got)
    for sid, h in got.items():
        assert h == golden[sid], f"shard {sid} diverged from golden"

    # shards actually landed on REMOTE servers (not just the source)
    remote_holders = {s.address for s in volumes if s is not src
                     for loc in s.store.locations
                     for sid in range(TEST_GEO.total_shards)
                     if os.path.exists(TEST_GEO.shard_file_name(
                         loc.base_name("strm", vid), sid))}
    assert remote_holders, "no shard streamed to a remote server"

    # zero client-visible errors through the EC read path
    for fid, payload in blobs.items():
        r = requests.get(f"http://{src.address}/{fid}", timeout=30)
        assert r.status_code == 200, (fid, r.status_code)
        assert r.content == payload

    # stream metrics moved
    assert stats.EC_STREAM_BYTES.value(role="source", phase="live") > 0
    assert stats.EC_STREAM_STREAMS.value(outcome="ok") > 0


def test_copy_path_matches_golden_and_counts_fallback(cluster, tmp_path):
    """-stream 0 (generate-then-copy) produces the same golden bytes and
    moves the like-for-like VolumeEcShardsCopy byte counters."""
    master, volumes, env = cluster
    vid, blobs, src = _make_volume(master, volumes, "copy", seed=2)
    base = _snapshot_dat(src, vid, tmp_path)
    golden = _golden_hashes(base, TEST_GEO)

    before = stats.EC_COPY_FALLBACK_BYTES.value(kind="shard")
    _encode(env, vid, "-stream 0")
    _wait_ec_registered(master, vid)
    assert stats.EC_COPY_FALLBACK_BYTES.value(kind="shard") > before
    assert stats.EC_COPY_FALLBACK_SECONDS.value() > 0

    got = _cluster_shard_hashes(volumes, vid, TEST_GEO, "copy")
    assert len(got) == TEST_GEO.total_shards
    for sid, h in got.items():
        assert h == golden[sid], f"shard {sid} diverged from golden"
    for fid, payload in blobs.items():
        r = requests.get(f"http://{src.address}/{fid}", timeout=30)
        assert r.status_code == 200 and r.content == payload


def test_copy_path_under_chunk_delay_keeps_bytes(cluster, tmp_path):
    """A per-chunk wire delay on the copy path (the `ec.copy.chunk`
    failpoint in delay mode) slows VolumeEcShardsCopy and changes no
    byte: every copied shard still hashes to the golden encode."""
    master, volumes, env = cluster
    vid, blobs, src = _make_volume(master, volumes, "cdly", seed=12)
    base = _snapshot_dat(src, vid, tmp_path)
    golden = _golden_hashes(base, TEST_GEO)

    with failpoint.active("ec.copy.chunk", mode="delay", p=0.002) as fp:
        _encode(env, vid, "-stream 0")
        assert fp.hits > 0, "no copied chunk went through the delay"
    _wait_ec_registered(master, vid)

    got = _cluster_shard_hashes(volumes, vid, TEST_GEO, "cdly")
    assert len(got) == TEST_GEO.total_shards
    for sid, h in got.items():
        assert h == golden[sid], f"shard {sid} diverged from golden"


# -- destination digests (.dig) ---------------------------------------------

def test_destination_digest_manifest_no_second_read(cluster, tmp_path):
    """Every streamed destination persists a `.dig` manifest whose folded
    CRCs equal a full-file CRC re-read, and VolumeDigest answers from
    it."""
    master, volumes, env = cluster
    vid, _blobs, src = _make_volume(master, volumes, "strm2", seed=3)
    _encode(env, vid, "-stream 1")
    _wait_ec_registered(master, vid)

    checked = 0
    for srv in volumes:
        if srv is src:
            continue
        for loc in srv.store.locations:
            base = loc.base_name("strm2", vid)
            if not os.path.exists(base + ".dig"):
                continue
            manifest = digest_mod.read_ec_manifest(base + ".dig")
            for sid, sc in manifest.items():
                path = TEST_GEO.shard_file_name(base, sid)
                with open(path, "rb") as f:
                    raw = f.read()
                assert len(raw) == sc.size
                assert crc32c(raw) == sc.crc, f"shard {sid} digest wrong"
                checked += 1
            # the VolumeDigest RPC serves these without a re-read
            stub = rpc.volume_stub(rpc.grpc_address(srv.address))
            from seaweedfs_tpu.pb import scrub_pb2

            resp = stub.VolumeDigest(
                scrub_pb2.VolumeDigestRequest(volume_id=vid), timeout=30)
            assert resp.is_ec
            got = {d.shard_id: (d.crc, d.size) for d in resp.shard_digests}
            for sid, sc in manifest.items():
                if sid in got:
                    assert got[sid] == (sc.crc, sc.size)
    assert checked > 0, "no destination manifest found"


def test_ec_manifest_format_golden():
    """Pin the on-disk EC digest manifest bytes."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "7")
        digest_mod.write_ec_manifest(base, {
            1: digest_mod.ShardCrc(1, 0xDEADBEEF, 123),
            0: digest_mod.ShardCrc(0, 7, 0)})
        with open(base + ".dig", "rb") as f:
            blob = f.read()
    assert blob == (
        b"SWFSDGE\n" + (2).to_bytes(8, "big")
        + (0).to_bytes(4, "big") + (7).to_bytes(4, "big")
        + (0).to_bytes(8, "big")
        + (1).to_bytes(4, "big") + (0xDEADBEEF).to_bytes(4, "big")
        + (123).to_bytes(8, "big"))
    # round-trip through the file reader
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "x.dig")
        with open(p, "wb") as f:
            f.write(blob)
        back = digest_mod.read_ec_manifest(p)
    assert back[1].crc == 0xDEADBEEF and back[1].size == 123
    assert back[0].crc == 7 and back[0].size == 0


# -- chaos: destination flap mid-stream + slab-range failpoint ---------------

def test_stream_resume_after_destination_flap(cluster, tmp_path):
    """Kill a destination mid-stream (ec.stream.slab failpoint): the
    source resumes from the destination's on-disk prefix, re-sends ONLY
    the missing range, final shards stay bit-identical, and the client
    sees zero errors."""
    master, volumes, env = cluster
    vid, blobs, src = _make_volume(master, volumes, "chaos", n_needles=40,
                                   seed=4, min_payload=140_000)
    base = _snapshot_dat(src, vid, tmp_path)
    dat_size = os.path.getsize(base + ".dat")
    # the stripe must cross the large-row boundary so slabs past offset
    # large_block exist (the flap target below)
    assert dat_size > TEST_GEO.large_block * TEST_GEO.data_shards, dat_size
    golden = _golden_hashes(base, TEST_GEO)

    resumes0 = stats.EC_STREAM_RESUMES.value()
    live0 = stats.EC_STREAM_BYTES.value(role="source", phase="live")
    resend0 = stats.EC_STREAM_BYTES.value(role="source", phase="resume")

    # one destination dies on the first small-row slab it sees (offset
    # large_block — AFTER every shard's first 10000 bytes landed), once;
    # the live stream to it aborts and the resume must start from the
    # on-disk prefix, never re-sending the completed large-row slabs
    with failpoint.active("ec.stream.slab", p=1.0, count=1,
                          match=f"off={TEST_GEO.large_block},") as fp:
        msg = _encode(env, vid, "-stream 1")
        assert fp.hits == 1, "destination never flapped"
    assert "resume" in msg, msg
    _wait_ec_registered(master, vid)

    assert stats.EC_STREAM_RESUMES.value() > resumes0
    resent = stats.EC_STREAM_BYTES.value(role="source",
                                         phase="resume") - resend0
    live = stats.EC_STREAM_BYTES.value(role="source", phase="live") - live0
    shard_size = TEST_GEO.shard_size(dat_size)
    total_shard_bytes = shard_size * TEST_GEO.total_shards
    assert resent > 0
    # only the missing tail ranges were re-sent: the flapped destination
    # already held every shard's large-row prefix, so the resume is far
    # smaller than even one destination's full share
    assert resent < total_shard_bytes / 2, (resent, total_shard_bytes)
    assert live > resent, (live, resent)

    got = _cluster_shard_hashes(volumes, vid, TEST_GEO, "chaos")
    assert len(got) == TEST_GEO.total_shards
    for sid, h in got.items():
        assert h == golden[sid], f"shard {sid} diverged after resume"
    for fid, payload in blobs.items():
        r = requests.get(f"http://{src.address}/{fid}", timeout=30)
        assert r.status_code == 200 and r.content == payload


def test_stream_slab_failpoint_matches_shard_and_range(cluster):
    """The ec.stream.slab ctx is matchable per shard AND per slab offset
    (comma-terminated, so shard=1 can't substring-hit shard 10)."""
    # grammar: the comma-terminated ctx cannot substring-collide
    fp = failpoint._Failpoint("ec.stream.slab", "error", 1.0, -1,
                              "shard=1, off=0,", None)
    assert fp.should_trigger("localhost:1, shard=1, off=0,")
    assert not fp.should_trigger("localhost:1, shard=10, off=0,")
    assert not fp.should_trigger("localhost:1, shard=1, off=10000,")

    # live: target the first slab of ANY shard at a remote destination
    # (alternative grammar), once — the stream resumes and converges
    master, volumes, env = cluster
    vid, _blobs, _src = _make_volume(master, volumes, "slab", seed=5)
    alts = "|".join(f"shard={i}, off=0," for i in range(14))
    with failpoint.active("ec.stream.slab", p=1.0, count=1,
                          match=alts) as live:
        _encode(env, vid, "-stream 1")
        assert live.hits == 1, "no targeted slab hit the failpoint"


def test_stream_hard_failure_falls_back_to_copy(cluster):
    """A destination that refuses every stream (failpoint without a
    count bound) is completed via the VolumeEcShardsCopy fallback —
    the archive still converges."""
    master, volumes, env = cluster
    vid, blobs, src = _make_volume(master, volumes, "fall", seed=6)
    old = os.environ.get("SWFS_EC_STREAM_RETRIES")
    os.environ["SWFS_EC_STREAM_RETRIES"] = "2"
    try:
        # no @match: EVERY destination refuses every slab (placement may
        # give any particular server zero shards, so targeting one
        # address can vacuously miss)
        with failpoint.active("ec.stream.slab", p=1.0):
            msg = _encode(env, vid, "-stream 1")
    finally:
        if old is None:
            os.environ.pop("SWFS_EC_STREAM_RETRIES", None)
        else:
            os.environ["SWFS_EC_STREAM_RETRIES"] = old
    assert "fallback copy" in msg, msg
    _wait_ec_registered(master, vid)
    for fid, payload in blobs.items():
        r = requests.get(f"http://{src.address}/{fid}", timeout=30)
        assert r.status_code == 200 and r.content == payload


# -- satellite: read-only rollback on failed encode --------------------------

@pytest.mark.parametrize("stream,fp_name", [
    (1, "pb.VolumeEcShardsGenerateStreamed"),
    (0, "pb.VolumeEcShardsGenerate"),
])
def test_failed_encode_rolls_back_readonly(cluster, stream, fp_name):
    """Regression (pre-ISSUE-6 bug): a generate/copy/mount failure left
    every replica read-only forever. Now the replicas are restored to
    writable and the volume keeps serving."""
    master, volumes, env = cluster
    vid, blobs, src = _make_volume(master, volumes, "roll", seed=7 + stream)
    v = src.store.find_volume(vid)
    assert not v.read_only
    with failpoint.active(fp_name, p=1.0, count=1):
        out = io.StringIO()
        code = run_command(env, f"ec.encode -volumeId {vid} "
                                f"-stream {stream}", out)
        assert code != 0, "encode unexpectedly succeeded"
    assert not v.read_only, "replica left read-only after failed encode"
    # the plain volume still serves
    fid, payload = next(iter(blobs.items()))
    r = requests.get(f"http://{src.address}/{fid}", timeout=30)
    assert r.status_code == 200 and r.content == payload
    # and a retry without the failpoint completes the conversion
    _encode(env, vid, f"-stream {stream}")
    _wait_ec_registered(master, vid)


# -- observability ------------------------------------------------------------

def test_status_and_metrics_expose_ec_stream(cluster):
    master, volumes, _env = cluster
    st = requests.get(f"http://{volumes[0].address}/status",
                      timeout=10).json()
    assert "EcStream" in st
    sect = st["EcStream"]
    for key in ("streamedBytes", "inflightBytes", "resumes", "streams",
                "overlapRatio", "copyFallback"):
        assert key in sect, sect
    text = requests.get(f"http://{volumes[0].address}/metrics",
                        timeout=10).text
    assert "SeaweedFS_ec_stream_bytes" in text
    assert "SeaweedFS_ec_shards_copy_bytes" in text
