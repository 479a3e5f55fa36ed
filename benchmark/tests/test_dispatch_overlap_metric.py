"""The four-chip cell's reader of the scheduler's overlapped batches, on
a tiny run over four virtual CPU devices: it reads a finite share of 0
or more traced and is absent untraced, as dispatch_lock_wait_ms.encode
is; a program without the counter gives nothing, and no error."""

import importlib.util
import math
import os

import pytest

import harness
import tiny

CELL = "concurrent8.encode-4chip"
READER = "dispatch_overlap_pct.encode"
COUNTER = "SeaweedFS_ec_dispatch_overlapped_batches"


def _reader():
    spec = importlib.util.spec_from_file_location(
        "reader_" + READER.replace(".", "_"),
        os.path.join(harness.BENCH, "metrics", READER + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_overlap_reader(tmp_path, trace):
    cell = tiny.cell(CELL)
    assert READER in {m["name"] for m in cell.per_layer}
    run, _ = tiny.run(cell, tmp_path, trace=trace)
    assert run.correct, run.checks
    res = harness.report(run, {"platform": "cpu", "kind": "cpu",
                               "count": 4}, emit=lambda _: None)
    if not trace:
        assert READER not in res["metrics"]
        return
    value = res["metrics"][READER]["value"]
    assert math.isfinite(value) and 0 <= value <= 100, value
    # the parent's program has no such counter
    for snapshot in (run.counters_before, run.counters_after):
        for key in [k for k in snapshot if k[0] == COUNTER]:
            del snapshot[key]
    assert _reader()(run) is None
