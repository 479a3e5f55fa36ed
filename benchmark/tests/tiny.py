"""Run a cell of BENCHMARK.json on the CPU at a tiny size."""

from __future__ import annotations

import copy
import os

import jax

import harness

TINY_CONFIG = {"volume_bytes": 3 << 20, "objects": 3000}
TINY_TRAFFIC = {"rate": 60, "warmup_seconds": 1, "trace_seconds": 1}


def cell(name: str, root: str = harness.ROOT, volumes: int | None = None):
    c = harness.load_cell(name, root)
    c.config = copy.deepcopy(c.config)
    for k, v in TINY_CONFIG.items():
        if k in c.config:
            c.config[k] = v
    if volumes is not None:
        c.config["volumes"] = volumes
    c.traffic = dict(c.traffic)
    for k, v in TINY_TRAFFIC.items():
        if k in c.traffic:
            c.traffic[k] = v
    return c


def run(c, tmp_path, seed: int = 3, seconds: float = 1.5,
        trace: bool = False, after_setup=None):
    chips = int(c.workload["chips"])
    lines = []
    r = harness.execute(c, seed, seconds, trace,
                        os.path.join(str(tmp_path), "work"), chips=chips,
                        devices=jax.devices()[:chips],
                        peaks=harness.peaks_for("TPU v5 lite"),
                        say=lines.append, after_setup=after_setup)
    return r, lines
