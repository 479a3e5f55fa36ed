"""The trace reduction: busy intervals, idle shares and gap labels, on a
hand-made trace and on a small trace recorded on a v5e."""

import os
from types import SimpleNamespace as NS

import pytest

import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ev(name, start_s, dur_s):
    return NS(name=name, start_ns=start_s * 1e9, duration_ns=dur_s * 1e9)


def _space():
    ops = NS(name="XLA Ops", events=[_ev("fusion.1", 1.0, 0.2),
                                     _ev("fusion.2", 1.1, 0.3),
                                     _ev("copy", 3.0, 0.5)])
    mods = NS(name="XLA Modules", events=[_ev("jit_encode", 0.9, 3.0)])
    host = NS(name="python3", events=[_ev("bench:window", 0.0, 5.0),
                                      _ev("bench:encode", 0.5, 1.5),
                                      _ev("bench:rebuild", 2.5, 2.0),
                                      _ev("other", 0.0, 5.0)])
    return NS(planes=[NS(name="/device:TPU:0", lines=[ops, mods]),
                      NS(name="/host:CPU", lines=[host]),
                      NS(name="/device:TPU:0 SparseCore 0", lines=[ops])])


def test_reduction_by_hand():
    tl = devtrace.Timeline.from_xspace(_space())
    assert list(tl.ops) == [0]
    assert tl.busy(0) == [(1.0, pytest.approx(1.4)), (3.0, 3.5)]
    assert tl.busy_in("encode", [0]) == [pytest.approx(0.4)]
    # encode in flight 1.5 s, busy 0.4 s; rebuild 2.0 s, busy 0.5 s
    assert tl.idle_pct("encode", [0]) == pytest.approx(100 * (1 - 0.4 / 1.5))
    assert tl.idle_pct("rebuild", [0]) == pytest.approx(75.0)
    assert tl.idle_pct("scrub", [0]) is None      # nothing to read
    assert tl.idle_pct("encode", [1]) is None     # no such device plane
    top = tl.top_ops("window", [0])
    assert top[0][0] == "copy" and top[0][1] == pytest.approx(0.5)
    gaps = tl.idle_gaps("window", [0])
    # 1.4 .. 3.0 between the calls, 3.5 .. 5.0 in the rebuild, 0 .. 1.0
    assert [g[0] for g in gaps] == ["chip 0: no call in flight",
                                    "chip 0: rebuild", "chip 0: encode"]
    assert [g[1] for g in gaps] == pytest.approx([1.6, 1.5, 1.0])


def test_reduction_of_a_trace_recorded_on_a_v5e():
    path = os.path.join(DATA, "v5e_small.xplane.pb")
    from jax.profiler import ProfileData

    tl = devtrace.Timeline.from_xspace(ProfileData.from_file(path))
    # one device plane; three launches of one fused op, ~51 us each
    assert list(tl.ops) == [0] and len(tl.ops[0]) == 3
    assert all(45e-6 < b - a < 60e-6 for a, b, _ in tl.ops[0])
    assert len(tl.spans("encode")) == 3 and len(tl.spans("window")) == 1
    # the device clock runs ~1.4 ms ahead of the host annotations here:
    # the first launch lands before the window opens, the others inside
    busy = tl.busy_in("window", [0])[0]
    assert busy == pytest.approx(sum(b - a for a, b, _ in tl.ops[0][1:]))
    idle = tl.idle_pct("window", [0])
    assert 99 < idle < 100
    (op, secs), = tl.top_ops("window", [0])
    assert op.startswith("%shift-right-logical_xor_fusion") and secs == busy
