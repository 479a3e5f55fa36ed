"""Write a sealed volume's .dat and .idx in bulk, in upstream's needle format.

Set-up has to make a volume of a million needles in seconds, so this does
not go through the HTTP write path, which no cell measures. The bytes are
the version-3 needle records of upstream's needle_write.go:

  header  cookie(4) id(8) size(4)                      big-endian
  body    dataSize(4) data flags(1) [mimeSize(1) mime] lastModified(5)
  tail    crc32c(data)(4) appendAtNs(8) zero padding to 8 bytes (1..8)

`size` covers the body. The .dat starts with the 8-byte superblock
(version 3, placement 000, no TTL). Each .idx entry is id(8)
offset/8(4) size(4). `benchmark/tests/test_volbuild.py` checks that the
files are byte-identical to what the program's own write path writes for
the same needles.
"""

from __future__ import annotations

import google_crc32c
import numpy as np

from dataset import Objects

VERSION = 3
FLAG_HAS_MIME = 0x04
FLAG_HAS_LAST_MODIFIED = 0x08
SUPER_BLOCK = bytes([VERSION, 0, 0, 0, 0, 0, 0, 0])


def _be(values: np.ndarray, width: int) -> np.ndarray:
    """[N] integers -> [N, width] big-endian bytes."""
    wide = np.asarray(values, np.uint64).astype(">u8").view(np.uint8)
    return wide.reshape(-1, 8)[:, 8 - width:]


def layout(objs: Objects, mime: bytes = b""):
    """(needle size fields, record lengths, .dat offsets, .dat size)."""
    n = len(objs.ids)
    extra = (1 + len(mime) if mime else 0) + 5
    body = 4 + objs.sizes + 1 + extra
    unpadded = 16 + body + 4 + 8
    records = unpadded + (8 - unpadded % 8)
    offsets = len(SUPER_BLOCK) + np.concatenate(
        ([0], np.cumsum(records)[:-1])).astype(np.int64)
    total = int(offsets[-1] + records[-1]) if n else len(SUPER_BLOCK)
    return body, records, offsets, total


def write_volume(base: str, objs: Objects, *, mime: bytes = b"",
                 last_modified: int, append_at_ns: int) -> int:
    """Write `<base>.dat` and `<base>.idx`; return the .dat size.

    Needle i is appended at `append_at_ns + i`."""
    n = len(objs.ids)
    sizes = objs.sizes
    flags = FLAG_HAS_LAST_MODIFIED | (FLAG_HAS_MIME if mime else 0)
    body, records, offsets, total = layout(objs, mime)

    prefix = np.concatenate([_be(objs.cookies, 4), _be(objs.ids, 8),
                             _be(body, 4), _be(sizes, 4)], axis=1)
    crcs = np.fromiter((google_crc32c.value(objs.payload(i).tobytes())
                        for i in range(n)), np.uint64, n)
    tail_parts = [np.full((n, 1), flags, np.uint8)]
    if mime:
        tail_parts.append(np.tile(np.frombuffer(
            bytes([len(mime)]) + mime, np.uint8), (n, 1)))
    tail_parts += [
        np.tile(_be(np.array([last_modified]), 5), (n, 1)),
        _be(crcs, 4),
        _be(append_at_ns + np.arange(n, dtype=np.uint64), 8)]
    suffix = np.concatenate(tail_parts, axis=1)

    buf = np.zeros(total, np.uint8)
    buf[:len(SUPER_BLOCK)] = np.frombuffer(SUPER_BLOCK, np.uint8)
    if n and (records == records[0]).all() and (sizes == sizes[0]).all():
        # every record alike: one strided view, no per-needle loop
        rec = int(records[0])
        s = int(sizes[0])
        rows = buf[len(SUPER_BLOCK):].reshape(n, rec)
        rows[:, :20] = prefix
        rows[:, 20:20 + s] = objs.data.reshape(n, s)
        rows[:, 20 + s:20 + s + suffix.shape[1]] = suffix
    else:
        for i in range(n):
            o = int(offsets[i])
            s = int(sizes[i])
            buf[o:o + 20] = prefix[i]
            buf[o + 20:o + 20 + s] = objs.data[
                objs.starts[i]:objs.starts[i] + s]
            buf[o + 20 + s:o + 20 + s + suffix.shape[1]] = suffix[i]
    idx = np.concatenate([_be(objs.ids, 8), _be(offsets // 8, 4),
                          _be(body, 4)], axis=1)
    with open(base + ".dat", "wb") as f:
        f.write(memoryview(buf))
    with open(base + ".idx", "wb") as f:
        f.write(memoryview(np.ascontiguousarray(idx)))
    return total
