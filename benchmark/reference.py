"""Plain reference for the benchmark's `correct`: Reed-Solomon over GF(2^8).

Written from the published description and independent of the program
(it imports nothing of `seaweedfs_tpu`):

- the field is GF(2^8) with the polynomial x^8+x^4+x^3+x^2+1 (0x11d) and
  generator 2, as in klauspost/reedsolomon, which upstream SeaweedFS uses;
- the encoding matrix is klauspost's default: the (k+m) x k Vandermonde
  matrix with rows [r^0, r^1, ..., r^(k-1)], times the inverse of its top
  k x k square, so the top k rows are the identity (systematic);
- the shard layout is upstream's ec_encoder.go: rows of `large_block`
  bytes per shard while more than one whole large row remains, then rows
  of `small_block`, the last row zero-padded.

`shard_digests` gives the sha256 of every shard file the reference would
write for a .dat file. The control (`matrix="cauchy"`) breaks the
configuration's guarantee that shards are byte-compatible with upstream:
it uses a systematic Cauchy matrix, the change that would tempt a faster
encoder, and every parity shard then differs.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, np.int32)
    log = np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[0:255]
    return exp, log


EXP, LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % 255])


def _mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = []
    for row in a:
        r = []
        for j in range(len(b[0])):
            acc = 0
            for t, v in enumerate(row):
                acc ^= gf_mul(v, b[t][j])
            r.append(acc)
        out.append(r)
    return out


def _mat_inv(m: list[list[int]]) -> list[list[int]]:
    n = len(m)
    work = [list(row) + [1 if i == j else 0 for j in range(n)]
            for i, row in enumerate(m)]
    for c in range(n):
        p = next(r for r in range(c, n) if work[r][c])
        work[c], work[p] = work[p], work[c]
        inv = gf_inv(work[c][c])
        work[c] = [gf_mul(v, inv) for v in work[c]]
        for r in range(n):
            if r != c and work[r][c]:
                f = work[r][c]
                work[r] = [v ^ gf_mul(f, w) for v, w in zip(work[r], work[c])]
    return [row[n:] for row in work]


def encoding_matrix(k: int, m: int, matrix: str = "vandermonde") -> np.ndarray:
    """The (k+m) x k systematic encoding matrix."""
    if matrix == "vandermonde":
        vm = [[gf_pow(r, c) for c in range(k)] for r in range(k + m)]
        return np.array(_mat_mul(vm, _mat_inv(vm[:k])), np.uint8)
    if matrix == "cauchy":
        rows = [[1 if i == j else 0 for j in range(k)] for i in range(k)]
        rows += [[gf_inv((k + i) ^ j) for j in range(k)] for i in range(m)]
        return np.array(rows, np.uint8)
    raise ValueError(f"unknown matrix {matrix!r}")


def _mul_tables(parity_rows: np.ndarray) -> np.ndarray:
    """[m, k, 256] product tables: tab[j, i, x] = parity_rows[j, i] * x."""
    m, k = parity_rows.shape
    tab = np.zeros((m, k, 256), np.uint8)
    for j in range(m):
        for i in range(k):
            c = int(parity_rows[j, i])
            tab[j, i] = [gf_mul(c, x) for x in range(256)]
    return tab


class Encoder:
    """Parity of [k, W] uint8 data, in column chunks over threads."""

    def __init__(self, k: int, m: int, matrix: str = "vandermonde",
                 threads: int | None = None):
        self.k, self.m = k, m
        self.tab = _mul_tables(encoding_matrix(k, m, matrix)[k:])
        self.threads = threads or min(16, os.cpu_count() or 1)

    def parity(self, data: np.ndarray, chunk: int = 1 << 20) -> np.ndarray:
        k, width = data.shape
        out = np.zeros((self.m, width), np.uint8)

        def job(c: int) -> None:
            for i in range(k):
                d = data[i, c:c + chunk]
                for j in range(self.m):
                    np.bitwise_xor(out[j, c:c + chunk], self.tab[j, i][d],
                                   out=out[j, c:c + chunk])

        with ThreadPoolExecutor(self.threads) as ex:
            list(ex.map(job, range(0, width, chunk)))
        return out


def row_blocks(dat_size: int, k: int, large_block: int,
               small_block: int) -> list[int]:
    """The per-shard block size of every row (ec_encoder.go:194-231)."""
    rows = []
    remaining = dat_size
    while remaining > large_block * k:
        rows.append(large_block)
        remaining -= large_block * k
    while remaining > 0:
        rows.append(small_block)
        remaining -= small_block * k
    return rows


def shard_digests(dat_path: str, k: int, m: int, large_block: int,
                  small_block: int, matrix: str = "vandermonde",
                  group_bytes: int = 256 << 20) -> list[str]:
    """sha256 of each of the k+m shard files for `dat_path`."""
    enc = Encoder(k, m, matrix)
    hashes = [hashlib.sha256() for _ in range(k + m)]
    dat_size = os.path.getsize(dat_path)
    rows = row_blocks(dat_size, k, large_block, small_block)
    with open(dat_path, "rb") as f, ThreadPoolExecutor(k + m) as hx:
        offset = 0
        i = 0
        while i < len(rows):
            block = rows[i]
            # a group of consecutive rows of one block size, or one large
            # row cut into column groups
            if block <= group_bytes // k:
                n = 1
                while (i + n < len(rows) and rows[i + n] == block
                       and (n + 1) * block * k <= group_bytes):
                    n += 1
                # the tail past the end of the .dat stays zero (padding)
                raw = np.zeros(n * block * k, np.uint8)
                f.seek(offset)
                f.readinto(memoryview(raw))
                data = np.ascontiguousarray(
                    raw.reshape(n, k, block).transpose(1, 0, 2)
                    .reshape(k, n * block))
                _update(hx, hashes, data, enc.parity(data))
                offset += n * block * k
                i += n
                continue
            step = group_bytes // k
            for col in range(0, block, step):
                width = min(step, block - col)
                data = np.zeros((k, width), np.uint8)
                for s in range(k):
                    f.seek(offset + block * s + col)
                    f.readinto(memoryview(data[s]))
                _update(hx, hashes, data, enc.parity(data))
            offset += block * k
            i += 1
    return [h.hexdigest() for h in hashes]


def _update(pool: ThreadPoolExecutor, hashes, data: np.ndarray,
            parity: np.ndarray) -> None:
    rows = [data[i] for i in range(data.shape[0])] + \
        [parity[j] for j in range(parity.shape[0])]
    list(pool.map(lambda hr: hr[0].update(np.ascontiguousarray(hr[1])),
                  zip(hashes, rows)))


def file_digest(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            chunk = f.read(16 << 20)
            if not chunk:
                return h.hexdigest()
            h.update(chunk)
