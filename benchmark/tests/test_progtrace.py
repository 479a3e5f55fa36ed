"""The program's spans in the profiler's trace, and the metrics that read
the EC pipeline's spans and counters: on a hand-made trace, on a small
trace recorded on a v5e, and on CPU runs of both cells."""

import importlib.util
import os
from types import SimpleNamespace as NS

import pytest

import devtrace
import harness
import progtrace
import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = {
    "archive-1g.encode-rebuild": [
        "pipeline_read_ms.encode", "pipeline_device_wait_ms.encode",
        "pipeline_write_ms.encode", "pipeline_read_ms.rebuild",
        "pipeline_device_wait_ms.rebuild", "pipeline_write_ms.rebuild",
        "dispatch_span_ms.encode"],
    "weedbench-1m.get-degraded": [
        "survivor_read_span_ms.get", "dispatch_wait_span_ms.get"],
}
DEVICE = ["idle_in_device_wait_pct.encode", "idle_in_device_wait_pct.rebuild"]


def _metric(name):
    path = os.path.join(harness.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ev(name, start_s, dur_s):
    return NS(name=name, start_ns=start_s * 1e9, duration_ns=dur_s * 1e9)


def _space():
    ops = NS(name="XLA Ops", events=[_ev("fusion", 1.0, 0.2),
                                     _ev("fusion", 2.0, 0.5)])
    bench = NS(name="python3", events=[_ev("bench:encode", 0.5, 2.5)])
    coord = NS(name="python3", events=[
        _ev("ec.encode.device_wait", 0.8, 0.6),     # 0.2 s of it busy
        _ev("ec.encode.device_wait", 2.6, 0.2),     # outside the busy op
        _ev("volume.ec.encode", 0.5, 2.5)])
    reader = NS(name="python3", events=[_ev("ec.encode.read", 0.5, 0.5)])
    return NS(planes=[NS(name="/device:TPU:0", lines=[ops]),
                      NS(name="/host:CPU", lines=[bench, coord, reader])])


def test_reduction_by_hand():
    space = _space()
    tl = devtrace.Timeline.from_xspace(space)
    spans = progtrace.from_xspace(space, progtrace.PIPELINE)
    assert set(spans) == {"ec.encode.device_wait", "ec.encode.read"}
    wait = spans["ec.encode.device_wait"]
    assert wait == [(0.8, pytest.approx(1.4)), (2.6, pytest.approx(2.8))]
    # the call: 0.5 .. 3.0, busy 0.7 s, idle 1.8 s; under the waits
    # 0.8 s, of it busy 0.2 s, so idle 0.6 s
    share = progtrace.idle_share_under(tl, "encode", wait, [0])
    assert share == pytest.approx(100 * 0.6 / 1.8)
    # read (0.5 .. 1.0) and the waits: under 0.5 .. 1.4 and 2.6 .. 2.8,
    # of which 0.2 s busy, so idle 0.9 of 1.8 s
    union = devtrace.union(wait + spans["ec.encode.read"])
    assert progtrace.idle_share_under(tl, "encode", union, [0]) == \
        pytest.approx(50.0)
    assert progtrace.idle_share_under(tl, "rebuild", wait, [0]) is None
    assert progtrace.idle_share_under(tl, "encode", [], [0]) is None
    assert progtrace.idle_share_under(tl, "encode", wait, [1]) is None
    assert progtrace.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2),
                                                               (3, 4)]


def test_reduction_of_a_trace_recorded_on_a_v5e():
    """A small encode through the program's pipeline, recorded on a v5e:
    the spans and the device ops share the file and the clock."""
    path = os.path.join(DATA, "v5e_pipeline.xplane.pb")
    from jax.profiler import ProfileData

    space = ProfileData.from_file(path)
    tl = devtrace.Timeline.from_xspace(space)
    spans = progtrace.from_xspace(space, progtrace.PIPELINE)
    slabs = len(spans["ec.encode.device_wait"])
    assert slabs == len(spans["ec.encode.read"]) == 3
    assert len(spans["ec.encode.write"]) == 14 * slabs
    assert len(spans["ec.dispatch.encode"]) >= 1
    assert list(tl.ops) == [0] and tl.ops[0]
    (call,) = tl.spans("encode")
    # every program span lies inside the call, on the same clock
    for iv in spans.values():
        assert all(call[0] <= a <= b <= call[1] for a, b in iv)
    share = progtrace.idle_share_under(tl, "encode",
                                       spans["ec.encode.device_wait"], [0])
    assert 0 < share < 100
    covered = progtrace.idle_share_under(
        tl, "encode", [iv for v in spans.values() for iv in v], [0])
    assert share < covered <= 100


def test_metrics_read_nothing_where_the_program_has_no_spans():
    """A program without the pipeline's spans and counters (an older
    checkout): every new metric leaves itself out, and none raises."""
    tl = devtrace.Timeline.from_xspace(NS(planes=[]))
    run = NS(delta=lambda *a, **kw: 0.0, span_ms=lambda name: [],
             timeline=tl, state={"program_spans": {}}, chips=1)
    for name in NEW["archive-1g.encode-rebuild"] + \
            NEW["weedbench-1m.get-degraded"] + DEVICE:
        assert _metric(name).read(run) is None, name


@pytest.fixture(autouse=True)
def small_recon_cache(monkeypatch):
    monkeypatch.setenv("SWFS_EC_RECON_CACHE_MB", "0.25")


@pytest.mark.parametrize("name", list(NEW))
def test_new_metrics_read_numbers_on_a_cpu_run(name, tmp_path):
    c = tiny.cell(name)
    assert set(NEW[name]) <= {m["name"] for m in c.per_layer}
    run, _ = tiny.run(c, tmp_path, trace=True)
    assert run.correct, run.checks
    res = harness.report(run, {"platform": "cpu", "kind": "cpu",
                               "count": 1}, emit=lambda _: None)
    for m in NEW[name]:
        assert res["metrics"][m]["value"] > 0, m
    if name.startswith("archive"):
        # the program's spans are in the CPU run's profiler trace too;
        # with no TPU plane the device shares leave themselves out
        spans = progtrace.of_run(run)
        assert spans["ec.encode.device_wait"] and \
            spans["ec.rebuild.device_wait"]
        assert not set(DEVICE) & set(res["metrics"])
