"""Open-loop GET load, run as a child process that never imports JAX.

Requests are due on a schedule drawn from the seed and are sent whether or
not earlier ones have been answered, as independent users send them. Each
is timed from the moment it was due to the moment its last body byte
arrived, so a stall in the server shows in the latency of every request
that fell due behind it (no coordinated omission). How late each request
was actually sent is reported apart, so that a starved generator is not
read as a fast server.

The client follows the volume server's 307, as a client of a volume
server does: the native data plane answers only registered volumes and
redirects the rest to the Python listener.

Protocol (one JSON object per line):
  stdin:  the job, then "go" once the parent has opened its window
  stdout: {"ready": true} after the warm-up, {"done": <results path>}
"""

from __future__ import annotations

import gc
import http.client
import json
import math
import sys
import threading
import time
from urllib.parse import urlsplit

import numpy as np

import dataset


def schedule(seed: int, rate: float, seconds: float, n_keys: int,
             stream: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """(due offsets in seconds, key indices) of one window.

    Arrivals are Poisson at `rate`, conditioned on the count: exactly
    round(rate * seconds) requests, whose gaps are exponential draws scaled
    to fill the window. Every seed then sends the same amount of work;
    only the order and spacing differ. Keys are uniform over all keys."""
    rng = np.random.Generator(np.random.SFC64([seed, stream, 0x10AD]))
    n = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0, n + 1)
    due = np.cumsum(gaps)[:-1] / gaps.sum() * seconds
    keys = rng.integers(0, n_keys, n)
    return due, keys


class _Client:
    """One keep-alive connection per host, following 307s."""

    def __init__(self, address: str):
        self.address = address
        self.conns: dict[str, http.client.HTTPConnection] = {}

    def _conn(self, hostport: str) -> http.client.HTTPConnection:
        c = self.conns.get(hostport)
        if c is None:
            host, port = hostport.rsplit(":", 1)
            c = self.conns[hostport] = http.client.HTTPConnection(
                host, int(port), timeout=60)
        return c

    def get(self, path: str) -> tuple[int, bytes]:
        hostport = self.address
        for _ in range(3):
            conn = self._conn(hostport)
            try:
                conn.request("GET", path)
                resp = conn.getresponse()
                body = resp.read()
            except (OSError, http.client.HTTPException):
                conn.close()
                self.conns.pop(hostport, None)
                raise
            if resp.status in (301, 302, 307, 308):
                loc = urlsplit(resp.getheader("Location", ""))
                hostport = loc.netloc or hostport
                path = loc.path + (f"?{loc.query}" if loc.query else "")
                continue
            return resp.status, body
        return 599, b""

    def close(self) -> None:
        for c in self.conns.values():
            c.close()


def run_schedule(clients: list[_Client], fids: list[str], due: np.ndarray,
                 keys: np.ndarray, keep_bodies: bool, lead: float = 0.05):
    """Send every request at its due time, one thread per client (whose
    connections stay open from one schedule to the next); return
    per-request (latency s from due, lateness s, status, body or None)."""
    n = len(due)
    lat = np.full(n, math.inf)
    late = np.zeros(n)
    status = np.zeros(n, np.int32)
    bodies: list = [None] * n
    nxt = [0]
    mu = threading.Lock()
    t0 = time.perf_counter() + lead  # time for the threads to start

    def worker(cl: _Client) -> None:
        while True:
            with mu:
                i = nxt[0]
                nxt[0] += 1
            if i >= n:
                return
            t_due = t0 + due[i]
            now = time.perf_counter()
            if now < t_due:
                time.sleep(t_due - now)
            sent = time.perf_counter()
            late[i] = sent - t_due
            try:
                st, body = cl.get("/" + fids[keys[i]])
            except (OSError, http.client.HTTPException):
                status[i] = 598
                continue
            status[i] = st
            lat[i] = time.perf_counter() - t_due
            if keep_bodies:
                bodies[i] = body

    threads = [threading.Thread(target=worker, args=(cl,), daemon=True)
               for cl in clients]
    for t in threads:
        t.start()
    for t in threads:
        # a request may be answered up to a minute past the window's close
        t.join(timeout=float(due[-1]) + 120)
    return lat, late, status, bodies


def main() -> int:
    job = json.loads(sys.stdin.readline())
    objs = dataset.objects(job["config"], job["seed"])
    fids = [objs.fid(job["vid"], i) for i in range(len(objs.ids))]
    # a million long-lived objects: keep the collector from walking them
    # in the middle of the window, which would stall the generator
    gc.freeze()
    pool = [_Client(job["address"]) for _ in range(job["clients"])]

    # warm-up: the same mix at the same rate, on its own stream of draws
    t0 = time.perf_counter()
    wdue, wkeys = schedule(job["seed"], job["rate"], job["warmup_seconds"],
                           len(fids), stream=1)
    run_schedule(pool, fids, wdue, wkeys, False)
    phases = {"paced warm-up": time.perf_counter() - t0}
    print(json.dumps({"ready": True, "phases": phases}), flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1

    due, keys = schedule(job["seed"], job["rate"], job["seconds"],
                         len(fids))
    gc.disable()
    lat, late, status, bodies = run_schedule(pool, fids, due, keys, True)
    gc.enable()
    for cl in pool:
        cl.close()
    # the comparison runs once the window has closed
    wrong = 0
    for i, body in enumerate(bodies):
        if status[i] != 200 or body is None or \
                body != objs.payload(int(keys[i])):
            wrong += 1
            lat[i] = math.inf  # reported as slower than every other
    out = {
        "scheduled": len(due),
        "latency_ms": [x * 1e3 if math.isfinite(x) else None for x in lat],
        "late_ms": {"p50": float(np.percentile(late, 50)) * 1e3,
                    "p99": float(np.percentile(late, 99)) * 1e3,
                    "max": float(late.max()) * 1e3},
        "wrong": wrong,
        "status": {str(s): int((status == s).sum())
                   for s in np.unique(status)},
    }
    with open(job["results"], "w") as f:
        json.dump(out, f)
    print(json.dumps({"done": job["results"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
