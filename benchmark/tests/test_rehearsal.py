"""Every cell of BENCHMARK.json, rehearsed on the CPU at a tiny size: set-up,
window, check, the fail-closed checks and the result line. The four-chip
cell runs on four virtual CPU devices."""

import json

import pytest

import harness
import tiny

with open(harness.ROOT + "/BENCHMARK.json") as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture(autouse=True)
def small_recon_cache(monkeypatch):
    # at a tiny size the 32 MiB reconstruct cache would hold every lost
    # block after the warm-up, and the degraded window would dispatch
    # nothing; a quarter MiB keeps the device path in the window
    monkeypatch.setenv("SWFS_EC_RECON_CACHE_MB", "0.25")


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(name, trace, tmp_path):
    c = tiny.cell(name)
    run, _ = tiny.run(c, tmp_path, trace=trace)
    assert run.correct, run.checks
    harness.fail_closed(run)
    lines = []
    chips = int(c.workload["chips"])
    res = harness.report(run, {"platform": "cpu", "kind": "cpu",
                               "count": chips}, emit=lines.append)
    assert res["correct"] and res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert any(line.startswith("ec batches by reason") for line in lines)
    if trace:
        # no TPU plane on the CPU: the device readers find nothing and
        # say so by leaving their metric out; the counters still read
        assert not any(k.startswith(("device_idle", "rs_"))
                       for k in res["metrics"])
        assert res["device"]["window_s"] > 0
    else:
        want = {m["name"] for m in c.end_to_end}
        assert set(res["metrics"]) == want
        assert all(v["value"] > 0 for v in res["metrics"].values())
