"""A later change adds a configuration, a traffic mix and a metric by
adding files and entries only; the harness finds them by name."""

import hashlib
import json
import os
import shutil

import harness
import tiny


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_mix_and_metric_need_no_edit(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(harness.BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    before = _digests(root)

    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / "archive-1g-rs10_4.json")
                     .read_text())
    cfg["volume_bytes"] = 2 << 20
    (bench / "configs" / "small-2m-rs10_4.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "encode-only.json").write_text(json.dumps({
        "driver": "ec_cycle", "rebuild": False, "warmup_rounds": 1,
        "trace_rounds": 1, "rounds": 1, "control": "encode_cauchy",
        "why": "encodes alone"}))
    (bench / "metrics" / "encode_calls.py").write_text(
        "def read(run):\n    return len(run.host.get('encodes') or []) "
        "or None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "small-2m-rs10_4", "source": "https://example.org/x",
        "file": "benchmark/configs/small-2m-rs10_4.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": "small-2m.encode-only", "config": "small-2m-rs10_4",
        "traffic": "encode-only", "chips": 1, "why": "test"})
    next(m for m in spec["end_to_end"] if m["name"] == "encode_GBps")[
        "workloads"].append("small-2m.encode-only")
    spec["per_layer"].append({
        "name": "encode_calls", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "gateway", "moves": "encode_GBps",
        "workloads": ["small-2m.encode-only"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(root)
    changed = [p for p in before if before[p] != after[p]]
    assert changed == ["BENCHMARK.json"]

    cell = harness.load_cell("small-2m.encode-only", str(root))
    assert [m["name"] for m in cell.per_layer] == ["encode_calls"]
    run, _ = tiny.run(cell, tmp_path, trace=True)
    assert run.correct
    res = harness.report(run, {"platform": "cpu", "kind": "cpu",
                               "count": 1}, emit=lambda _: None)
    assert res["metrics"]["encode_calls"]["value"] >= 1


def test_four_chip_cell_runs_from_its_entries(tmp_path):
    """The four-chip cell's files are in benchmark/ and its entries wait
    in tests/data until it is measured on the chip; added to a copy of
    BENCHMARK.json, they run it on four virtual CPU devices, traced and
    not, with every chip running encode batches."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(harness.BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    spec = json.loads(open(os.path.join(harness.ROOT, "BENCHMARK.json"))
                      .read())
    with open(os.path.join(harness.BENCH, "tests", "data",
                           "concurrent8_entries.json")) as f:
        entries = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        spec[key] += entries[key]
    name = entries["workloads"][0]["name"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in entries["also_in"]:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = tiny.cell(name, str(root))
    assert cell.workload["chips"] == 4
    for trace in (False, True):
        run, _ = tiny.run(cell, tmp_path, trace=trace)
        assert run.correct, run.checks
        harness.fail_closed(run)  # every chip ran encode batches
        res = harness.report(run, {"platform": "cpu", "kind": "cpu",
                                   "count": 4}, emit=lambda _: None)
        if trace:
            assert 0 < res["metrics"]["chip_batch_share_max.encode"][
                "value"] <= 100
        else:
            assert set(res["metrics"]) == {"setup_s", "encode_GBps"}
