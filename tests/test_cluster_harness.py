"""Tier-1 smoke for the fleet traffic harness (ISSUE 8 satellite): a
tiny 2-volume-server cluster under ~5s of the full mixed workload —
zipfian S3 reads, small-file PUT flood, archival ec.encode churn and a
degraded-read storm — asserting nonzero goodput per shape and a clean
shutdown. The harness is the instrument every cluster A/B depends on;
without this test it rots silently between runs.

Runs the harness as a SUBPROCESS (its own JAX_PLATFORMS=cpu, its own
port space, guaranteed teardown via its own signal handling) — the same
way an operator drives it from the command line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_HARNESS = os.path.join(_REPO, "tools", "cluster_harness.py")


def _last_json_line(text: str) -> dict | None:
    for line in reversed((text or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def test_harness_https_smoke_all_shapes():
    """ISSUE 9: the same four-shape smoke with the WHOLE cluster —
    public ingress, every internal leg, all four generators — moved
    onto TLS by the --https switch, handshake counters in the artifact
    proving the encrypted plane actually carried the traffic."""
    proc = subprocess.run(
        [sys.executable, _HARNESS, "--smoke", "--https", "--servers",
         "2", "--duration", "5", "--vol-mb", "1"],
        cwd=_REPO, capture_output=True, text=True, timeout=270,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "SEAWEEDFS_TPU_NATIVE": "0"})
    out = _last_json_line(proc.stdout)
    assert out is not None, (proc.stdout[-500:], proc.stderr[-500:])
    assert "error" not in out, out["error"]
    assert out["https"] is True
    assert out["clean_shutdown"] is True
    for name, s in out["shapes"].items():
        assert s["ok"] > 0, f"shape {name} zero goodput over TLS: {s}"
    hs = out["handshakes"]
    # the spawned servers ACCEPTED handshakes (their listeners wrapped
    # real connections) and some in-cluster client leg dialed TLS
    assert sum(v.get("server", 0)
               for v in hs["per_server"].values()) > 0, hs
    assert hs["harness_client"] > 0 or any(
        v.get("client", 0) > 0 for v in hs["per_server"].values()), hs


def test_harness_tls_flap_zero_client_errors():
    """ISSUE 9 chaos satellite: a volume server restarted with a
    ROTATED cert (same CA) mid-read-storm — handshake/EOF/connection
    flakes retry, the rotated cert serves, certificate-verification
    failures fail fast, zero client-visible errors."""
    proc = subprocess.run(
        [sys.executable, _HARNESS, "--tls-flap", "--servers", "1"],
        cwd=_REPO, capture_output=True, text=True, timeout=270,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "SEAWEEDFS_TPU_NATIVE": "0"})
    out = _last_json_line(proc.stdout)
    assert out is not None, (proc.stdout[-500:], proc.stderr[-500:])
    assert "error" not in out, out
    assert out["client_errors"] == 0, out
    assert out["reads_ok"] > 0 and out["reads_after_restart"] > 0, out
    # the restart was actually disruptive: at least one flake retried
    assert out["flakes_retried"] >= 1, out
    assert out["rotated"] is True, out
    # the PR-2 classification end-to-end: wrong trust root -> immediate
    # non-retryable failure, not a retry storm
    assert out["fail_fast_verified"] is True, out
    assert out["fail_fast_seconds"] < 5, out
    assert out["clean_shutdown"] is True, out


def test_harness_crash_drill_smoke():
    """ISSUE 16 tentpole: two kill-anywhere rounds (torn dat append +
    SIGKILL mid-group-commit) against a live 2-server cluster. Contract:
    every ACKED write reads back byte-identical after the crashed
    server restarts, unacked in-flight writes are all-or-nothing, and
    the victim reports the unclean startup via /status.Recovery."""
    proc = subprocess.run(
        [sys.executable, _HARNESS, "--crash-drill", "--smoke",
         "--servers", "2"],
        cwd=_REPO, capture_output=True, text=True, timeout=400,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "SEAWEEDFS_TPU_NATIVE": "0"})
    out = _last_json_line(proc.stdout)
    assert out is not None, (proc.stdout[-500:], proc.stderr[-500:])
    assert "error" not in out, out
    assert out["ackedTotal"] > 0
    assert out["ackedLost"] == 0 and out["partialVisible"] == 0
    assert out["corruptReads"] == 0
    # both armed sites actually SIGKILLed the victim mid-operation...
    assert len(out["sitesHit"]) == 2, out["sitesHit"]
    for rd in out["rounds"]:
        assert rd.get("exit") == -9, rd
        assert rd.get("crashMarker") is True, rd
    # ...and both restarts detected the unclean shutdown and ran the
    # recovery ladder before serving
    assert out["uncleanRecoveries"] == 2, out
    assert out["clean_shutdown"] is True, out


def test_harness_metadata_smoke_two_shards():
    """ISSUE 19 tentpole: a 2-shard partitioned filer namespace under
    the deep-path create/list/stat + rename-churn storm, every leg
    routed by the master-published metadata ring. Contract: nonzero
    goodput, zero errors (every read sha-verified), ops actually served
    by BOTH shards, and zero client-visible wrong-shard answers after
    the one-stale-retry 410+epoch ladder."""
    proc = subprocess.run(
        [sys.executable, _HARNESS, "--metadata", "--smoke",
         "--servers", "1", "--duration", "5"],
        cwd=_REPO, capture_output=True, text=True, timeout=270,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "SEAWEEDFS_TPU_NATIVE": "0"})
    out = _last_json_line(proc.stdout)
    assert out is not None, (proc.stdout[-500:], proc.stderr[-500:])
    assert "error" not in out, out["error"]
    assert out["filerShards"] == 2
    md = out["shapes"]["metadata"]
    assert md["ok"] > 0 and md["errors"] == 0, md
    # the data-plane shapes ride the partitioned namespace unharmed
    for name in ("put_flood", "zipf_read"):
        s = out["shapes"][name]
        assert s["ok"] > 0 and s["errors"] == 0, (name, s)
    # traffic genuinely spread across the ring
    assert len(out["okByShard"]) >= 2, out["okByShard"]
    assert out["wrongShardClientErrors"] == 0, out
    # both shards published the same ring picture at the same epoch
    rings = [v["MetaShard"]["ring"]
             for v in out["shardStatus"].values() if v.get("MetaShard")]
    assert len(rings) == 2 and rings[0] == rings[1], rings
    assert len(rings[0]["shards"]) == 2, rings[0]
    assert out["clean_shutdown"] is True, out


def test_harness_smoke_all_shapes_and_clean_shutdown():
    # subprocess timeout is the watchdog here (no pytest-timeout in the
    # container); the conftest 300s faulthandler backstops the backstop
    proc = subprocess.run(
        [sys.executable, _HARNESS, "--smoke", "--servers", "2",
         "--duration", "5", "--vol-mb", "1"],
        cwd=_REPO, capture_output=True, text=True, timeout=270,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "SEAWEEDFS_TPU_NATIVE": "0"})
    out = _last_json_line(proc.stdout)
    assert out is not None, (proc.stdout[-500:], proc.stderr[-500:])
    assert "error" not in out, out["error"]
    assert out["clean_shutdown"] is True, \
        "a server had to be SIGKILLed at teardown"
    shapes = out["shapes"]
    assert set(shapes) == {"zipf_read", "put_flood", "archival",
                           "degraded_read", "bigfile"}
    for name, s in shapes.items():
        assert s["ok"] > 0, f"shape {name} produced zero goodput: {s}"
        assert s["offered"] >= s["ok"]
        # foreground + degraded shapes report latency percentiles
        if name != "archival":
            assert s.get("p50_ms", 0) > 0 and s.get("p99_ms", 0) > 0
    # the open-loop shapes must not silently collapse into errors:
    # transient churn is tolerated, an error-dominated run is not
    # (bigfile errors include sha mismatches — the ISSUE-14 pipelined
    # path's identity contract rides the same bound)
    for name in ("zipf_read", "put_flood", "degraded_read", "bigfile"):
        s = shapes[name]
        assert s["errors"] <= max(2, 0.1 * s["offered"]), \
            f"shape {name} error-dominated: {s}"
