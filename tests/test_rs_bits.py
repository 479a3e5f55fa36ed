"""Bit-identity of the bitsliced GF(2^8) matmul (ops/rs_jax) vs an oracle.

`_dispatch_matmul` is the runtime-operand form every reconstruct and every
chip-pinned dispatch runs; `RSCodecJax` adds the constant-folded encode.
Encode and decode matrices, several geometries, byte counts on both sides
of every `_bucket` edge, chip-pinned dispatches and the LRC geometry are
checked against the gf256 / rs_cpu oracles.
"""

import numpy as np
import pytest

from seaweedfs_tpu.models import geometry as gm
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_cpu import RSCodecCPU
from seaweedfs_tpu.ops.rs_jax import RSCodecJax, _dispatch_matmul
from seaweedfs_tpu.parallel.mesh import local_devices


def _oracle(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    out = np.zeros((matrix.shape[0], data.shape[1]), dtype=np.uint8)
    for r in range(matrix.shape[0]):
        acc = np.zeros(data.shape[1], dtype=np.uint8)
        for c in range(matrix.shape[1]):
            acc ^= gf256.gf_mul_vec(
                np.full_like(data[c], matrix[r, c]), data[c]
            )
        out[r] = acc
    return out


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (12, 4), (3, 2)])
def test_xla_matches_oracle(k, m):
    rng = np.random.default_rng(k * 100 + m)
    matrix = gf256.parity_matrix(k, m)
    data = rng.integers(0, 256, size=(k, 4096), dtype=np.uint8)
    got = np.asarray(_dispatch_matmul(matrix, data))
    np.testing.assert_array_equal(got, _oracle(matrix, data))


# 8, 9, 511, 512, 1025 and 65537 sit on the edges of `_bucket`: the
# power-of-two steps below 512, the 512 bucket itself, and one past a
# power-of-two multiple of it, where padding is widest
@pytest.mark.parametrize("b", [1, 3, 4, 513, 4096,
                               8, 9, 511, 512, 1025, 65537])
def test_xla_odd_lengths(b):
    rng = np.random.default_rng(b)
    matrix = gf256.parity_matrix(10, 4)
    data = rng.integers(0, 256, size=(10, b), dtype=np.uint8)
    got = np.asarray(_dispatch_matmul(matrix, data))
    assert got.shape == (4, b)
    np.testing.assert_array_equal(got, _oracle(matrix, data))


def test_decode_matrix_identity():
    rng = np.random.default_rng(9)
    k, m = 10, 4
    matrix = gf256.parity_matrix(k, m)
    data = rng.integers(0, 256, size=(k, 2048), dtype=np.uint8)
    parity = _oracle(matrix, data)
    shards = np.concatenate([data, parity], axis=0)
    present = [i for i in range(k + m) if i not in (0, 5, 11, 13)]
    dec, used = gf256.decode_matrix_for(k, m, present)
    stacked = shards[list(used)]
    got = np.asarray(_dispatch_matmul(dec, stacked))
    np.testing.assert_array_equal(got, data)


@pytest.mark.parametrize("lost", [(1, 4, 10, 12), (0, 6, 9, 13)])
def test_codec_encode_reconstruct_roundtrip(lost):
    """RSCodecJax encode against the oracle, then four shards (data and
    parity) rebuilt in one fused reconstruct."""
    rng = np.random.default_rng(sum(lost))
    coder = RSCodecJax(10, 4)
    data = rng.integers(0, 256, size=(10, 20000), dtype=np.uint8)
    shards = np.asarray(coder.encode(data))
    np.testing.assert_array_equal(
        shards[10:], _oracle(gf256.parity_matrix(10, 4), data))
    present = {i: shards[i] for i in range(14) if i not in lost}
    rebuilt = coder.reconstruct(present)
    assert sorted(rebuilt) == sorted(lost)
    for i in lost:
        np.testing.assert_array_equal(np.asarray(rebuilt[i]), shards[i])


@pytest.mark.parametrize("chip", [0, 3])
@pytest.mark.parametrize("op", ["encode", "reconstruct_stacked"])
def test_chip_pinned_matches_cpu(op, chip):
    """The per-chip lane form: the dispatch is committed to one device
    and its bit operand served from the device-resident cache; bytes
    match rs_cpu and the result lives on that device."""
    device = local_devices()[chip]
    rng = np.random.default_rng(40 + chip)
    ref = RSCodecCPU(10, 4)
    coder = RSCodecJax(10, 4)
    data = rng.integers(0, 256, size=(10, 3000), dtype=np.uint8)
    if op == "encode":
        out = coder.encode_parity(data, device=device)
        want = ref.encode_parity(data)
    else:
        shards = np.concatenate([data, ref.encode_parity(data)])
        present = tuple(i for i in range(14) if i not in (0, 5, 10))
        missing, out = coder.reconstruct_stacked(
            present, shards[list(present)], device=device)
        assert missing == (0, 5, 10)
        want = shards[list(missing)]
    assert out.devices() == {device}
    np.testing.assert_array_equal(np.asarray(out), want)


def test_lrc_encode_and_local_repair():
    """lrc_10_2_2 through RSCodecJax: parity matches rs_cpu, and a lost
    data shard is rebuilt from its local group alone."""
    lrc = gm.get("lrc_10_2_2")
    rng = np.random.default_rng(61)
    data = rng.integers(0, 256, size=(10, 5000), dtype=np.uint8)
    ref = RSCodecCPU(10, 4, geometry=lrc)
    coder = RSCodecJax(10, 4, geometry=lrc)
    parity = np.asarray(coder.encode_parity(data))
    np.testing.assert_array_equal(parity, ref.encode_parity(data))
    shards = np.concatenate([data, parity])
    plan = lrc.repair_plan((2,), tuple(i for i in range(14) if i != 2))
    assert len(plan.reads) < 10, plan.reads
    missing, rows = coder.reconstruct_stacked(
        plan.reads, shards[list(plan.reads)], want=(2,))
    assert tuple(missing) == (2,)
    np.testing.assert_array_equal(np.asarray(rows)[0], shards[2])
