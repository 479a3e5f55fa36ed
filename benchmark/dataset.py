"""The objects of a configuration, made from the seed.

The same (configuration, seed, volume index) always gives the same needle
ids, cookies, sizes and payload bytes. volbuild.py writes them and
the GET checker compares every response body with them, so both call
`objects` and neither reads the other's files. Imports numpy only: the
load-generator child process uses it and must stay off JAX.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Objects:
    ids: np.ndarray       # uint64 needle ids (keys)
    cookies: np.ndarray   # uint32
    sizes: np.ndarray     # int64 payload bytes
    starts: np.ndarray    # int64 offset of each payload in `data`
    data: np.ndarray      # uint8, every payload back to back

    def payload(self, i: int) -> memoryview:
        s = int(self.starts[i])
        return memoryview(self.data[s:s + int(self.sizes[i])])

    def fid(self, vid: int, i: int) -> str:
        b = int(self.ids[i]).to_bytes(8, "big") + \
            int(self.cookies[i]).to_bytes(4, "big")
        j = 0
        while j < 8 and b[j] == 0:
            j += 1
        return f"{vid},{b[j:].hex()}"


def _random_bytes(rng: np.random.Generator, n: int) -> np.ndarray:
    words = rng.bit_generator.random_raw((n + 7) // 8)
    return words.view(np.uint8)[:n]


def objects(cfg: dict, seed: int, volume: int = 0) -> Objects:
    """Needles of volume `volume` of the configuration `cfg`.

    `object_sizes` in the configuration is either {"fixed": S}, with
    `objects` objects, or {"log_uniform": [lo, hi]}: sizes drawn
    log-uniform until their payloads hold `volume_bytes`."""
    rng = np.random.Generator(np.random.SFC64([seed, volume, 0x5EED]))
    spec = cfg["object_sizes"]
    if "fixed" in spec:
        n = int(cfg["objects"])
        sizes = np.full(n, int(spec["fixed"]), np.int64)
    else:
        lo, hi = (float(np.log(v)) for v in spec["log_uniform"])
        fill = int(cfg["volume_bytes"])
        # draw in bulk: the mean of a log-uniform size is (hi-lo)/ln(hi/lo)
        mean = (np.exp(hi) - np.exp(lo)) / (hi - lo)
        draws = np.exp(rng.uniform(lo, hi, int(fill / mean * 1.3) + 64))
        sizes = draws.astype(np.int64)
        total = np.cumsum(sizes)
        n = int(np.searchsorted(total, fill)) + 1
        if n > len(sizes):
            raise ValueError("log-uniform draw fell short of volume_bytes")
        sizes = sizes[:n]
    starts = np.concatenate(([0], np.cumsum(sizes)[:-1])).astype(np.int64)
    ids = np.arange(1, n + 1, dtype=np.uint64)
    cookies = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    data = _random_bytes(rng, int(sizes.sum()))
    return Objects(ids, cookies, sizes, starts, data)
