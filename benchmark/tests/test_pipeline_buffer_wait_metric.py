"""The reader of the encode pipeline's wait for recycled slab buffers,
on synthetic counter snapshots: seconds waited over slabs, in ms, across
every pipeline; nothing where the program has no such counter, as the
parent does, and nothing where the window held no encode slab."""

import importlib.util
import os

import pytest

import harness

READER = "pipeline_buffer_wait_ms.encode"
WAIT = "SeaweedFS_ec_pipeline_buffer_wait_seconds"
SLABS = "SeaweedFS_ec_pipeline_slabs"


def _reader():
    spec = importlib.util.spec_from_file_location(
        "reader_" + READER.replace(".", "_"),
        os.path.join(harness.BENCH, "metrics", READER + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(before: dict, after: dict) -> harness.Run:
    run = harness.Run(cell=None, seed=0, seconds=30.0, trace=True,
                      workdir="")
    run.counters_before, run.counters_after = before, after
    return run


def _snapshot(wait_encode, wait_rebuild, slabs_encode, slabs_rebuild,
              counter=True):
    """Samples keyed as the harness keys /metrics: (name, label pairs)."""
    out = {(SLABS, frozenset({("op", "encode")})): slabs_encode,
           (SLABS, frozenset({("op", "rebuild")})): slabs_rebuild}
    if counter:
        out[(WAIT, frozenset({("op", "encode")}))] = wait_encode
        out[(WAIT, frozenset({("op", "rebuild")}))] = wait_rebuild
    return out


def test_reads_ms_per_encode_slab_from_deltas():
    """0.25 s more of encode wait over 200 more encode slabs is 1.25 ms;
    the rebuild's wait and slabs do not count."""
    run = _run(_snapshot(1.0, 5.0, 100, 10),
               _snapshot(1.25, 9.0, 300, 50))
    assert _reader()(run) == pytest.approx(1.25)


@pytest.mark.parametrize("counter", [False, True],
                         ids=["no_counter", "no_encode_slab"])
def test_none_without_counter_or_slabs(counter):
    """The parent's program has no such counter: no value and no error;
    a window with no encode slab has none either."""
    if counter:
        run = _run(_snapshot(1.0, 0.0, 100, 0), _snapshot(1.0, 2.0, 100, 9))
    else:
        run = _run(_snapshot(0, 0, 100, 0, counter=False),
                   _snapshot(0, 0, 300, 0, counter=False))
    assert _reader()(run) is None
