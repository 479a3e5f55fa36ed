"""volbuild.py writes what the program's write path writes."""

import json
import os
import time

import pytest

import dataset
import harness
import volbuild


@pytest.mark.parametrize("config", ["archive-1g-rs10_4",
                                    "weedbench-1m-1k-rs10_4"])
def test_bulk_volume_is_byte_identical_to_the_write_path(tmp_path, config,
                                                         monkeypatch):
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    with open(os.path.join(harness.BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg.update(volume_bytes=1 << 20, objects=300)
    objs = dataset.objects(cfg, seed=2**31 + 77)
    mime = cfg["mime"].encode()
    t_ns = cfg["last_modified"] * 10**9

    bulk = tmp_path / "bulk"
    bulk.mkdir()
    size = volbuild.write_volume(str(bulk / "1"), objs, mime=mime,
                                 last_modified=cfg["last_modified"],
                                 append_at_ns=t_ns)

    # the write path stamps appendAtNs = max(now, last + 1): hold the
    # clock at volbuild's first stamp so both lay down the same bytes
    served = tmp_path / "served"
    served.mkdir()
    monkeypatch.setattr(time, "time_ns", lambda: t_ns)
    v = Volume(str(served), "", 1)
    for i in range(len(objs.ids)):
        v.write_needle(Needle.create(
            int(objs.ids[i]), int(objs.cookies[i]),
            objs.payload(i).tobytes(), mime=mime,
            last_modified=cfg["last_modified"]))
    v.close()

    for ext in (".dat", ".idx"):
        want = (served / f"1{ext}").read_bytes()
        assert (bulk / f"1{ext}").read_bytes() == want, ext
    assert size == len((served / "1.dat").read_bytes())
