"""The system under test, started in this process, and what the benchmark
reads from it: counters (the Prometheus text the program serves) and
finished spans (its in-process trace store).

The master and one volume server start here, as an operator's `weed
server` would, with the default "tpu" coder and the native data plane.
The process that calls `start` holds the chip.
"""

from __future__ import annotations

import re
import socket
import time
from dataclasses import dataclass

_SAMPLE = re.compile(r'^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})? (\S+)$')
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


@dataclass
class Cluster:
    master: object
    volume: object
    stub: object

    @property
    def public(self) -> str:
        return self.volume.address

    def stop(self) -> None:
        from seaweedfs_tpu.pb import rpc

        try:
            self.volume.stop()  # closes the store's dispatch schedulers
        finally:
            self.master.stop()
            rpc.reset_channels()


def start(workdir: str, max_volumes: int) -> Cluster:
    """Master plus one volume server over `workdir`, which already holds
    the sealed volumes; returns once the volume server has registered."""
    from seaweedfs_tpu.pb import rpc
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer

    master = MasterServer(ip="localhost", port=free_port())
    master.start(vacuum_interval=3600)
    vsrv = VolumeServer(directories=[workdir],
                        master=f"localhost:{master.port}", ip="localhost",
                        port=free_port(), max_volume_counts=[max_volumes],
                        native=True)
    try:
        vsrv.start()
        deadline = time.time() + 60
        while time.time() < deadline and not master.topo.nodes:
            time.sleep(0.05)
        if not master.topo.nodes:
            raise RuntimeError("volume server did not register")
    except BaseException:
        vsrv.stop()
        master.stop()
        raise
    return Cluster(master, vsrv,
                   rpc.volume_stub(rpc.grpc_address(vsrv.address)))


def counters() -> dict[tuple, float]:
    """Every sample the program's /metrics text holds, keyed by
    (name, frozenset of label pairs)."""
    from seaweedfs_tpu.utils import stats

    out: dict[tuple, float] = {}
    for line in stats.gather().splitlines():
        if not line or line[0] == "#":
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        labels = frozenset(_LABEL.findall(m.group(2) or ""))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            continue
    return out


def spans_since(t0: float, t1: float) -> list:
    """(name, start perf_counter, duration ms) of the spans that finished
    in this process and started inside [t0, t1]."""
    from seaweedfs_tpu.utils import trace

    store = trace.STORE
    with store._lock:
        ring = list(store._ring)
    return [(s.name, s._t0, s.duration_ms) for s in ring
            if t0 <= s._t0 <= t1 and s.duration_ms >= 0]


def batches(snapshot: dict, label: str, **match) -> dict[str, float]:
    """EC dispatch batches in `snapshot` split by one label."""
    return split(snapshot, "SeaweedFS_ec_dispatch_batches", label, **match)


def split(snapshot: dict, name: str, label: str, **match) -> dict[str, float]:
    want = set(match.items())
    out: dict[str, float] = {}
    for (n, labels), v in snapshot.items():
        if n != name or not want <= labels:
            continue
        d = dict(labels)
        if label in d:
            out[d[label]] = out.get(d[label], 0.0) + v
    return out


def total(snapshot: dict, name: str, **match) -> float:
    want = set(match.items())
    return sum(v for (n, labels), v in snapshot.items()
               if n == name and want <= labels)


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0)}
