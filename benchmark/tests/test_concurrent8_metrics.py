"""The four-chip cell's readers of the scheduler's lock wait and the read
pool's queue, on a tiny run over four virtual CPU devices: each reads a
finite value of 0 or more traced and is absent untraced, as
chip_batch_share_max.encode is; a program without their counters gives
nothing, and no error."""

import importlib.util
import math
import os

import pytest

import harness
import tiny

CELL = "concurrent8.encode-4chip"
READERS = ("dispatch_lock_wait_ms.encode", "read_queue_ms.encode")
COUNTERS = ("SeaweedFS_ec_dispatch_lock_wait_seconds",
            "SeaweedFS_ec_pipeline_read_queue_seconds")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(harness.BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_lock_wait_and_read_queue_readers(tmp_path, trace):
    cell = tiny.cell(CELL)
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
    run, _ = tiny.run(cell, tmp_path, trace=trace)
    assert run.correct, run.checks
    res = harness.report(run, {"platform": "cpu", "kind": "cpu",
                               "count": 4}, emit=lambda _: None)
    if not trace:
        assert not set(READERS) & set(res["metrics"])
        return
    for name in READERS:
        value = res["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    # the parent's program has neither counter
    for snapshot in (run.counters_before, run.counters_after):
        for key in [k for k in snapshot if k[0] in COUNTERS]:
            del snapshot[key]
    assert [_reader(name)(run) for name in READERS] == [None, None]
