"""`correct` can fail: the reference agrees with the program's own oracle,
each cell's control comes out not correct, and so does each fault the
cell can have, planted in the timed path underneath a whole run."""

import json

import numpy as np
import pytest

import controls
import harness
import reference
import tiny

with open(harness.ROOT + "/BENCHMARK.json") as f:
    CELLS = {w["name"]: w for w in json.load(f)["workloads"]}


@pytest.fixture(autouse=True)
def small_recon_cache(monkeypatch):
    monkeypatch.setenv("SWFS_EC_RECON_CACHE_MB", "0.25")


def test_reference_matches_the_programs_oracle():
    from seaweedfs_tpu.ops.rs_cpu import RSCodecCPU

    data = np.random.default_rng(4).integers(0, 256, (10, 4096), np.uint8)
    want = np.asarray(RSCodecCPU(10, 4).encode_parity(data), np.uint8)
    assert np.array_equal(reference.Encoder(10, 4).parity(data, 1000), want)
    cauchy = reference.Encoder(10, 4, "cauchy").parity(data)
    assert not np.array_equal(cauchy, want)
    # the reference decoder inverts what the reference encoder wrote
    full = np.concatenate([data, want])
    present = (1, 2, 3, 4, 6, 7, 8, 9, 11, 12, 13)
    rows = controls.reference_decoder(10, 4, "vandermonde")(
        present, full[list(present)], (0, 5, 10))
    assert np.array_equal(rows, full[[0, 5, 10]])


@pytest.mark.parametrize("name", list(CELLS))
def test_control_is_not_correct(name, tmp_path):
    c = tiny.cell(name)
    run, _ = tiny.run(c, tmp_path,
                      after_setup=controls.control(c.traffic["control"]))
    assert not run.correct
    assert any(v > lim for _, v, lim in run.checks)


def _wrap(run, names, post):
    coder = run.state["cluster"].volume.store.coder
    for n in names:
        orig = getattr(coder, n)

        def wrapped(*a, _orig=orig, **kw):
            return post(_orig(*a, **kw))
        setattr(coder, n, wrapped)


ENCODES = ["encode_parity", "encode_parity_on", "encode_parity_stacked",
           "encode_parity_stacked_on"]
RECONSTRUCTS = ["reconstruct_stacked", "reconstruct_stacked_on",
                "reconstruct_stacked_vsharded"]


def _flip(a):
    a = np.array(a, np.uint8)
    a.reshape(-1)[a.size // 2] ^= 0x5A
    return a


def _half(a):
    a = np.array(a, np.uint8)
    a[..., a.shape[-1] // 2:] = 0
    return a


def _answer_altered(run):
    if run.traffic["driver"] == "ec_cycle":
        _wrap(run, ENCODES, _flip)
    else:
        controls.patch_read(run, lambda d: d[:-1] + bytes([d[-1] ^ 1]))


def _half_batch(run):
    if run.traffic["driver"] == "ec_cycle":
        _wrap(run, ENCODES, _half)
    else:
        _wrap(run, RECONSTRUCTS, lambda out: (out[0], _half(out[1])))


def _state_unchanged(run, monkeypatch):
    from seaweedfs_tpu.server import volume as server_volume
    from seaweedfs_tpu.storage.ec_files import EncodeStats

    # the conversion call returns having written no shard
    monkeypatch.setattr(server_volume, "write_ec_files",
                        lambda *a, **kw: EncodeStats())


FAULTS = [(name, fault) for name, w in CELLS.items()
          for fault in (["answer_altered", "half_batch", "state_unchanged"]
                        if "encode" in w["traffic"] else
                        ["answer_altered", "half_batch"]
                        if w["traffic"] == "get-degraded" else
                        ["answer_altered"])]


@pytest.mark.parametrize("name,fault", FAULTS)
def test_fault_is_not_correct(name, fault, tmp_path, monkeypatch):
    c = tiny.cell(name)
    hook = {"answer_altered": _answer_altered, "half_batch": _half_batch,
            "state_unchanged": lambda r: _state_unchanged(r, monkeypatch)
            }[fault]
    run, _ = tiny.run(c, tmp_path, after_setup=hook)
    assert not run.correct, run.checks
