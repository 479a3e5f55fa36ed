"""The controls of `correct`: the plain reference put in the program's
place with one guarantee of the configuration broken. Each must come out
not correct; the benchmark's own runs never run them.

    python3 benchmark/controls.py --workload <name> --seeds 1,2,3 \\
        --seconds <s>

runs the cell at its own size on the chip this process holds, with the
control named by the cell's traffic file ("control") switched on after
set-up, and prints each run's compared numbers beside their limits.

- encode_cauchy: parity from the reference encoder with a systematic
  Cauchy matrix, the faster-to-build matrix that would tempt a change,
  in place of the program's coder: shards no longer match upstream's.
- reconstruct_cauchy: degraded reads decoded by the reference with the
  Cauchy matrix, in place of the program's reconstruct.

The fault tests (tests/test_correctness.py) use the same patches with a
program fault in place of the reference.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import reference


def _missing(k: int, n: int, present, data_only: bool, want) -> tuple:
    if want is not None:
        return tuple(want)
    limit = k if data_only else n
    return tuple(i for i in range(limit) if i not in present)


def patch_coder(run, encode=None, reconstruct=None) -> None:
    """Route the store's coder through `encode(data[k, W]) -> [m, W]`
    and `reconstruct(present, rows[P, W], missing) -> [len(missing), W]`,
    on every entry the dispatch scheduler may call."""
    coder = run.state["cluster"].volume.store.coder
    k, m = coder.data_shards, coder.parity_shards

    if encode is not None:
        def enc2(data, *_a, **_k):
            return encode(np.asarray(data, np.uint8))

        def enc3(stack, *_a, **_k):
            return np.stack([encode(np.asarray(s, np.uint8)) for s in stack])
        coder.encode_parity = enc2
        coder.encode_parity_on = enc2
        coder.encode_parity_stacked = enc3
        coder.encode_parity_stacked_on = enc3
    if reconstruct is not None:
        def rec2(present_ids, stacked, data_only=False, device=None,
                 want=None):
            missing = _missing(k, k + m, present_ids, data_only, want)
            return missing, reconstruct(tuple(present_ids),
                                        np.asarray(stacked, np.uint8),
                                        missing)

        def rec3(present_ids, stack, data_only=False, want=None):
            outs = [rec2(present_ids, s, data_only, want=want)
                    for s in np.asarray(stack, np.uint8)]
            missing = outs[0][0] if outs else _missing(
                k, k + m, present_ids, data_only, want)
            return missing, np.stack([o[1] for o in outs])
        coder.reconstruct_stacked = rec2
        coder.reconstruct_stacked_on = rec2
        coder.reconstruct_stacked_vsharded = rec3


def patch_read(run, alter) -> None:
    """Every needle the volume server reads passes through `alter`."""
    srv = run.state["cluster"].volume
    orig = srv.read_needle

    def read_needle(*a, **kw):
        n = orig(*a, **kw)
        n.data = alter(bytes(n.data))
        return n
    srv.read_needle = read_needle


def reference_decoder(k: int, m: int, matrix: str):
    """reconstruct(present, rows, missing) by the reference: invert the
    encoding matrix's rows of the first k survivors."""
    full = reference.encoding_matrix(k, m, matrix)

    def reconstruct(present, rows, missing):
        use = list(present[:k])
        inv = reference._mat_inv([list(map(int, full[i])) for i in use])
        want = [reference._mat_mul([list(map(int, full[r]))], inv)[0]
                for r in missing]
        enc = reference.Encoder(k, len(missing))
        enc.tab = reference._mul_tables(np.array(want, np.uint8))
        return enc.parity(np.ascontiguousarray(rows[:k]))
    return reconstruct


def control(name: str):
    """after_setup hook that switches the named control on."""
    def on(run) -> None:
        k = run.config["data_shards"]
        m = run.config["parity_shards"]
        if name == "encode_cauchy":
            enc = reference.Encoder(k, m, "cauchy")
            patch_coder(run, encode=enc.parity)
        elif name == "reconstruct_cauchy":
            patch_coder(run, reconstruct=reference_decoder(k, m, "cauchy"))
        else:
            raise ValueError(f"unknown control {name!r}")
    return on


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Run a cell's control.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    import harness

    cell = harness.load_cell(args.workload)
    chips = int(cell.workload["chips"])
    try:
        devices, _, peaks = harness.open_chips(chips)
    except harness.BenchFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    name = cell.traffic["control"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = harness.execute(
            cell, seed, args.seconds, False,
            os.path.join(harness.BENCH, "out", "work"), chips=chips,
            devices=devices, peaks=peaks,
            say=lambda line: print(line, file=sys.stderr),
            after_setup=control(name))
        print(json.dumps({
            "workload": args.workload, "control": name, "seed": seed,
            "correct": run.correct, "attempted": run.attempted,
            "seconds": time.perf_counter() - t0,
            "checks": {n: {"value": v, "limit": lim}
                       for n, v, lim in run.checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
