"""Eight concurrent conversions on the mesh size a v5e 2x2 host ships:
a ShardedCoder over four devices (the first four of the forced host
platform, tests/conftest.py) and eight `generate_ec_files` pipelines at
once, as eight volumes converted together on one volume server.

Checked: every .ec00-.ec13 byte against the rs_cpu oracle, every chip's
encode batches, and the two waits this deployment adds: flushes waiting
for the scheduler's one dispatch lock, and slab reads queueing on the
shared `ec_read` pool.
"""

import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import dispatch
from seaweedfs_tpu.parallel.mesh import ShardedCoder, local_devices, make_mesh
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.utils import stats

from .test_ec_pipeline import _make_synthetic_volume
from .test_ec_slab_fill import GEO, K, M, _oracle_shards, _shards
from .test_ec_slab_fill import fresh_read_pool  # noqa: F401 — fixture

CHIPS = 4
VOLUMES = 8


@pytest.fixture(autouse=True)
def _clean_schedulers():
    yield
    dispatch.shutdown_all()


def test_eight_concurrent_conversions_on_four_chips(tmp_path, monkeypatch,
                                                     fresh_read_pool):
    if len(local_devices()) < CHIPS:
        pytest.skip("needs the forced multi-device host platform")
    # several read streams a slab, so the slabs go through the pool
    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    coder = ShardedCoder(K, M, mesh=make_mesh(local_devices()[:CHIPS]))
    assert len(coder.placement_devices()) == CHIPS
    bases = [str(tmp_path / f"v{i}") for i in range(VOLUMES)]
    for i, base in enumerate(bases):
        _make_synthetic_volume(base, n_needles=16, seed=60 + i)

    batches0 = stats.EC_DISPATCH_BATCHES.split_by("chip", lane="encode")
    waited0 = stats.EC_DISPATCH_LOCK_WAIT.value(lane="encode")
    queued0 = stats.EC_PIPELINE_READ_QUEUE.value(op="encode")
    errors = []
    barrier = threading.Barrier(VOLUMES)

    def convert(base):
        try:
            barrier.wait()
            ec_files.generate_ec_files(base, coder, GEO, batch_size=50)
        except BaseException as e:  # noqa: BLE001 — raised below
            errors.append(e)

    threads = [threading.Thread(target=convert, args=(b,)) for b in bases]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[0]

    for base in bases:
        with open(base + ".dat", "rb") as f:
            want = _oracle_shards(f.read())
        got = _shards(base)
        for i in range(K + M):
            assert got[i] == want[i], f"{base} shard {i} differs"
    batches1 = stats.EC_DISPATCH_BATCHES.split_by("chip", lane="encode")
    for c in range(CHIPS):
        assert batches1.get(str(c), 0) > batches0.get(str(c), 0), \
            f"chip {c} ran no encode batch: {batches1}"
    assert stats.EC_DISPATCH_LOCK_WAIT.value(lane="encode") > waited0
    assert stats.EC_PIPELINE_READ_QUEUE.value(op="encode") > queued0
