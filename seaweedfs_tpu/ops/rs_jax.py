"""TPU-native Reed-Solomon codec: GF(2^8) matmul as a bitsliced GF(2) matmul.

The reference's hot loop (`enc.Encode` on 14x256KB buffers,
/root/reference/weed/storage/erasure_coding/ec_encoder.go:162-192) is
parity[m, B] = G[m, k] (x) data[k, B] over GF(256). TPUs have no carry-less
byte multiply, but every GF(256) constant c acts on a byte x as an 8x8 bit
matrix over GF(2):  bits(c*x) = M_c @ bits(x) mod 2,  with
M_c[i, j] = bit_i(c * 2^j).  Stacking those per-coefficient blocks turns the
whole shard computation into ONE dense GF(2) matmul:

    parity_bits[8m, B] = BigM[8m, 8k] @ data_bits[8k, B]  mod 2

which maps straight onto the MXU as an int8 x int8 -> int32 dot followed by
`& 1`. The matrix is tiny (<= 128x256 for RS(32, ...)) and constant-folded
per geometry; B (bytes per shard in a batch) is the large dimension.

This one primitive serves the library's whole 4-call surface
(Encode / Reconstruct / ReconstructData / Verify): encode uses the parity
generator block, reconstruction uses host-inverted decode matrices
(gf256.decode_matrix_for) — inverses are unique, so outputs stay
bit-identical to the Go path.
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256

# The byte axis is padded up to the next power-of-two multiple of this before
# the jitted matmul and sliced after. Bounds XLA recompilation to O(log B)
# distinct shapes (needle intervals have arbitrary sizes) and keeps the lane
# dimension tile-aligned.
_BYTE_BUCKET = 512


def _bucket(b: int) -> int:
    if b <= _BYTE_BUCKET:
        n = 8
        while n < b:
            n *= 2
        return n
    n = _BYTE_BUCKET
    while n < b:
        n *= 2
    return n


def _pad_bytes(data, b: int):
    padded = _bucket(b)
    if padded == b:
        return data
    return jnp.pad(data, ((0, 0), (0, padded - b)))


def gf_matrix_to_bits(m: np.ndarray) -> np.ndarray:
    """Expand a GF(256) matrix [R, C] to its GF(2) action matrix [8R, 8C].

    Block (r, c) is the 8x8 bit matrix of the constant m[r, c]:
    out[8r+i, 8c+j] = bit_i(m[r,c] * 2^j).
    """
    m = np.asarray(m, dtype=np.uint8)
    r, c = m.shape
    powers = np.array([1 << j for j in range(8)], dtype=np.uint8)  # [8]
    # prod[r, c, j] = m[r,c] * 2^j in GF(256)
    prod = gf256.gf_mul_vec(m[:, :, None], powers[None, None, :])
    # bits[r, c, i, j] = bit i of prod[r, c, j]
    bits = (prod[:, :, None, :] >> np.arange(8)[None, None, :, None]) & 1
    big = bits.transpose(0, 2, 1, 3).reshape(8 * r, 8 * c)
    return big.astype(np.int8)


def _unpack_bits(data: jax.Array) -> jax.Array:
    """[k, B] uint8 -> [8k, B] int8 of 0/1; row 8d+j is bit j of shard d."""
    k, b = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    bits = (data[:, None, :] >> shifts) & jnp.uint8(1)
    return bits.reshape(8 * k, b).astype(jnp.int8)


def _pack_bits(bits: jax.Array) -> jax.Array:
    """[8r, B] int (0/1) -> [r, B] uint8."""
    r8, b = bits.shape
    bits = bits.reshape(r8 // 8, 8, b).astype(jnp.uint8)
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    return jnp.bitwise_xor.reduce(bits << shifts, axis=1)


def gf_matmul_bits(matrix_bits: jax.Array, data: jax.Array) -> jax.Array:
    """out[R, B] = GFmat([R,C]) (x) data[C, B], with matrix given in bit form.

    matrix_bits: [8R, 8C] int8 (from gf_matrix_to_bits)
    data:        [C, B] uint8
    returns:     [R, B] uint8
    """
    bits = _unpack_bits(data)  # [8C, B] int8
    acc = jax.lax.dot_general(
        matrix_bits,
        bits,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _pack_bits(acc & 1)


@functools.lru_cache(maxsize=1024)
def decode_matrix_cached(
    data_shards: int, parity_shards: int, present: tuple[int, ...]
) -> tuple[np.ndarray, tuple[int, ...]]:
    """Cached byte-form decode matrix for a survivor set: host Gauss-Jordan
    inversion run once per (geometry, survivor set)."""
    dec, used = gf256.decode_matrix_for(data_shards, parity_shards, list(present))
    return dec, tuple(used)


# Derived bit-form kernel operands, cached by the compact identity of the
# matrix — ("parity", k, m) or ("dec", k, m, present) — so the hot path
# never re-serializes or re-expands matrix contents.
# LRU eviction: hot keys (the encode parity matrix) survive survivor-set churn.
_DERIVED_MAX = 4096
_derived_forms: "collections.OrderedDict[tuple, np.ndarray]" = (
    collections.OrderedDict()
)
_derived_lock = threading.Lock()


def _derived(key: tuple, matrix: np.ndarray) -> np.ndarray:
    with _derived_lock:
        got = _derived_forms.get(key)
        if got is not None:
            _derived_forms.move_to_end(key)
            return got
    got = gf_matrix_to_bits(matrix)
    with _derived_lock:
        while len(_derived_forms) >= _DERIVED_MAX:
            _derived_forms.popitem(last=False)
        _derived_forms[key] = got
    return got


# Device-RESIDENT kernel operands for the per-chip dispatch lanes
# (ops/dispatch.py, ISSUE 5): a survivor set's fused decode matrix (or the
# encode parity operand) is uploaded to its assigned chip once and reused
# by every later dispatch pinned there. LRU so survivor-set churn can't
# pin one chip's memory full of dead matrices.
_DEVICE_OPS_MAX = 256
_device_ops: "collections.OrderedDict[tuple, jax.Array]" = (
    collections.OrderedDict()
)
_device_ops_lock = threading.Lock()


def _op_on_device(op_key: tuple, host_op: np.ndarray, device) -> jax.Array:
    """The derived operand `host_op` (identified by `op_key`), committed
    to `device` — cached, LRU-evicted."""
    key = (op_key, device)
    with _device_ops_lock:
        got = _device_ops.get(key)
        if got is not None:
            _device_ops.move_to_end(key)
            return got
    arr = jax.device_put(host_op, device)
    with _device_ops_lock:
        while len(_device_ops) >= _DEVICE_OPS_MAX:
            _device_ops.popitem(last=False)
        _device_ops[key] = arr
    return arr


@functools.lru_cache(maxsize=1024)
def fused_reconstruct_matrix(
    data_shards: int, parity_shards: int, present: tuple[int, ...],
    missing: tuple[int, ...]
) -> tuple[np.ndarray, tuple[int, ...]]:
    """Byte-form [len(missing), k] matrix taking the k survivors straight
    to every missing shard — data AND parity — in ONE GF matmul.

    Data rows come from the decode matrix; parity rows fold the parity
    generator through it (G_p @ dec), so reconstruct needs no second
    encode dispatch (round-3 VERDICT item 4). GF arithmetic is exact:
    outputs are bit-identical to the two-pass decode+re-encode (the
    reference's shape, ec_encoder.go:233-287 / store_ec.go:384).
    Cached per (geometry, survivor set, missing set)."""
    dec, used = decode_matrix_cached(data_shards, parity_shards, present)
    out = np.empty((len(missing), data_shards), dtype=np.uint8)
    parity_idx = [j for j, i in enumerate(missing) if i >= data_shards]
    for j, i in enumerate(missing):
        if i < data_shards:
            out[j] = dec[i]
    if parity_idx:
        gp = gf256.parity_matrix(data_shards, parity_shards)
        rows = [missing[j] - data_shards for j in parity_idx]
        out[parity_idx] = gf256.gf_matmul(gp[rows], dec)
    return out, used


def fused_reconstruct_op(
    data_shards: int, parity_shards: int, present: tuple[int, ...],
    missing: tuple[int, ...]
) -> tuple[np.ndarray, tuple[int, ...]]:
    """Cached bit-form fused reconstruct operand."""
    fmat, used = fused_reconstruct_matrix(
        data_shards, parity_shards, present, missing)
    op = _derived(("fdec", data_shards, parity_shards, present, missing),
                  fmat)
    return op, used


@functools.lru_cache(maxsize=512)
def fused_reconstruct_stacked_matrix(
    data_shards: int, parity_shards: int, present_ids: tuple[int, ...],
    limit: int,
) -> tuple[tuple[int, ...], np.ndarray]:
    """Byte-form [missing, len(present_ids)] matrix operating on
    survivors stacked in the CALLER's row order: the fused matrix's
    columns are permuted to that order, with zero columns for surplus
    survivors — so a pre-stacked buffer needs no device gather."""
    missing = tuple(i for i in range(limit) if i not in set(present_ids))
    if not missing:
        return (), np.zeros((0, len(present_ids)), np.uint8)
    fmat, used = fused_reconstruct_matrix(
        data_shards, parity_shards, tuple(sorted(present_ids)), missing)
    col_of = {s: c for c, s in enumerate(used)}
    pm = np.zeros((len(missing), len(present_ids)), np.uint8)
    for j, s in enumerate(present_ids):
        c = col_of.get(s)
        if c is not None:
            pm[:, j] = fmat[:, c]
    return missing, pm


def fused_reconstruct_stacked_op(
    data_shards: int, parity_shards: int, present_ids: tuple[int, ...],
    limit: int,
) -> tuple[tuple[int, ...], np.ndarray]:
    """Cached bit-form of the stacked (column-permuted) operand."""
    missing, pm = fused_reconstruct_stacked_matrix(
        data_shards, parity_shards, present_ids, limit)
    if not missing:
        return missing, pm
    op = _derived(("fdecs", data_shards, parity_shards, present_ids,
                   missing), pm)
    return missing, op


def parity_matrix_op(data_shards: int, parity_shards: int) -> np.ndarray:
    """Cached bit-form parity-matrix operand."""
    gp = gf256.parity_matrix(data_shards, parity_shards)
    return _derived(("parity", data_shards, parity_shards), gp)


# -- geometry-general operands (ISSUE 11) ------------------------------------
#
# Non-RS code geometries (models/geometry.py) ride the exact same kernel
# machinery with their own generator matrices; cache keys carry the
# geometry NAME so rs_10_4's keys — and therefore its bytes and its
# compiled kernels — are untouched. The RS paths above stay the oracle.


def geom_parity_key(geom) -> tuple:
    return ("gparity", geom.name)


def geom_parity_op(geom) -> np.ndarray:
    """Bit-form parity operand for an arbitrary code geometry."""
    return _derived(geom_parity_key(geom), geom.parity_matrix())


@functools.lru_cache(maxsize=2048)
def geom_stacked_matrix(geom, present_ids: tuple[int, ...],
                        targets: tuple[int, ...]) -> np.ndarray:
    """Byte-form [len(targets), len(present_ids)] repair matrix in the
    CALLER's survivor row order (models.geometry.repair_matrix is
    already column-ordered by its `present_ids` argument)."""
    return geom.repair_matrix(present_ids, targets)


def geom_stacked_op(geom, present_ids: tuple[int, ...],
                    targets: tuple[int, ...]) -> np.ndarray:
    pm = geom_stacked_matrix(geom, present_ids, targets)
    return _derived(("gdecs", geom.name, present_ids, targets), pm)


def geom_targets_for(geom, present_ids: tuple[int, ...],
                     data_only: bool, want) -> tuple[int, ...]:
    """The rows a stacked reconstruct solves: `want` verbatim, else the
    complement of the survivor set under the data/total limit."""
    if want is not None:
        return tuple(want)
    limit = geom.data_shards if data_only else geom.total_shards
    return tuple(i for i in range(limit) if i not in set(present_ids))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _encode_jit(data: jax.Array, data_shards: int, parity_shards: int) -> jax.Array:
    gp = gf256.parity_matrix(data_shards, parity_shards)
    big = jnp.asarray(gf_matrix_to_bits(gp))  # constant-folded per geometry
    return gf_matmul_bits(big, data)


@jax.jit
def _apply_matrix_jit(matrix_bits: jax.Array, data: jax.Array) -> jax.Array:
    return gf_matmul_bits(matrix_bits, data)


@functools.partial(jax.jit, donate_argnums=(1,))
def _apply_matrix_jit_donated(matrix_bits: jax.Array,
                              data: jax.Array) -> jax.Array:
    """`_apply_matrix_jit` with the data buffer DONATED: the caller's
    committed copy is retired at execution (see `_donate_wanted`). Used
    only on the dispatch scheduler's committed-input (device-pinned)
    path."""
    return gf_matmul_bits(matrix_bits, data)


_donation_quiet = False


def _donate_wanted() -> bool:
    """Donation of committed flush inputs, restricted to
    accelerator backends: the CPU client zero-copies page-aligned host
    buffers into device arrays, so a donated CPU "buffer" could be the
    dispatch scheduler's arena memory itself — never hand XLA a buffer
    the arena may recycle. XLA treats a donated input whose size
    matches no output as a deallocate-eagerly hint (parity rows != data
    rows here), which is exactly the point: retire the transfer buffer
    at execution."""
    global _donation_quiet
    if jax.default_backend() == "cpu":
        return False
    if not _donation_quiet:
        import warnings

        # expected by design: no output aliases the donated input's
        # size, so XLA notes it cannot reuse the buffer for outputs —
        # the eager deallocation still happens, the warning is noise
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        _donation_quiet = True
    return True


def _dispatch_matmul(matrix: np.ndarray, data: jax.Array, key: tuple = None,
                     device=None) -> jax.Array:
    """Padded bitsliced GF matmul with a runtime matrix operand.
    `matrix` is the byte-form GF(256) matrix; `key` is its compact cache
    identity (defaults to hashing the contents). With `device`, the
    computation is pinned to that chip (inputs committed there; the bit
    operand served from the device-resident LRU) — the per-chip lane
    form used by the EC dispatch scheduler."""
    if key is None:
        key = ("raw", matrix.shape, matrix.tobytes())
    b = data.shape[1]
    bits_np = _derived(key, matrix)
    donate = False
    if device is not None:
        data = jax.device_put(data, device)
        # the committed copy is ours alone — donate it so XLA retires
        # the transfer buffer at execution (device residency, ISSUE 12)
        donate = _donate_wanted()
        matrix_bits = _op_on_device(key, bits_np, device)
    else:
        matrix_bits = jnp.asarray(bits_np)
    fn = _apply_matrix_jit_donated if donate else _apply_matrix_jit
    return fn(matrix_bits, _pad_bytes(data, b))[:, :b]


class RSCodecJax:
    """klauspost-compatible RS codec with a JAX/TPU execution backend.

    Mirrors the 4-call surface the reference uses
    (SURVEY.md section 2; /root/reference/weed/storage/store_ec.go:384):
    encode / reconstruct / reconstruct_data / verify, operating on
    [total, B] or [k, B] uint8 arrays rather than Go byte-slice lists.
    """

    def __init__(self, data_shards: int = 10, parity_shards: int = 4,
                 geometry=None):
        if data_shards <= 0 or parity_shards < 0:
            raise ValueError("bad geometry")
        if data_shards + parity_shards > 256:
            raise ValueError("at most 256 total shards in GF(256)")
        from ..models import geometry as geom_mod

        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.geometry = geom_mod.as_geometry(data_shards, parity_shards,
                                             geometry)

    @property
    def geometry_id(self) -> str:
        return self.geometry.name

    # -- Encode ------------------------------------------------------------

    def encode_parity(self, data: np.ndarray | jax.Array,
                      device=None) -> jax.Array:
        """data [k, B] uint8 -> parity [m, B] uint8 (device array).
        `device` pins the dispatch to one chip (per-chip lanes)."""
        if device is not None:
            # commit to the target chip BEFORE any jnp op: an uncommitted
            # asarray would land on the default device and make chip 0
            # the serialization point the per-chip lanes exist to remove
            data = jax.device_put(np.asarray(data, np.uint8), device)
        data = jnp.asarray(data, dtype=jnp.uint8)
        assert data.shape[0] == self.data_shards, data.shape
        b = data.shape[1]
        if not self.geometry.is_rs:
            # non-RS geometry: same kernels, its own generator matrix
            # (cache keys carry the geometry name, never (k, m))
            return _dispatch_matmul(
                self.geometry.parity_matrix(), data,
                key=geom_parity_key(self.geometry), device=device)
        if device is not None:
            gp = gf256.parity_matrix(self.data_shards, self.parity_shards)
            key = ("parity", self.data_shards, self.parity_shards)
            return _dispatch_matmul(gp, data, key=key, device=device)
        out = _encode_jit(_pad_bytes(data, b), self.data_shards, self.parity_shards)
        return out[:, :b]

    def encode_parity_stacked(
        self, stack: np.ndarray | jax.Array, device=None
    ) -> jax.Array:
        """stack [V, k, B] -> parity [V, m, B] in ONE device dispatch.

        Parity is a per-byte-column GF matmul, so the V slabs are laid
        side by side along the column axis ([k, V*B]) and encoded as one
        batch — the dispatch-amortization primitive behind
        ops/dispatch.py: V volumes' concurrent encode pipelines pay one
        device round-trip instead of V. Columns are independent, so each
        slab's bytes are identical to its own encode_parity call.
        `device` pins the whole stacked dispatch to one chip — the
        device-affine sub-dispatch form the scheduler's per-chip lanes
        flush through."""
        if device is not None:
            # commit FIRST (see encode_parity): the swapaxes/reshape
            # below must run on the lane's chip, not the default device
            stack = jax.device_put(np.asarray(stack, np.uint8), device)
        stack = jnp.asarray(stack, dtype=jnp.uint8)
        assert stack.ndim == 3 and stack.shape[1] == self.data_shards, \
            stack.shape
        v, k, b = stack.shape
        wide = jnp.swapaxes(stack, 0, 1).reshape(k, v * b)
        parity = self.encode_parity(wide, device=device)
        return jnp.swapaxes(
            parity.reshape(self.parity_shards, v, b), 0, 1)

    def encode(self, shards: np.ndarray | jax.Array) -> jax.Array:
        """[k, B] data or [total, B] shards: fills parity rows, returns all."""
        shards = jnp.asarray(shards, dtype=jnp.uint8)
        assert shards.shape[0] in (self.data_shards, self.total_shards), shards.shape
        parity = self.encode_parity(shards[: self.data_shards])
        return jnp.concatenate([shards[: self.data_shards], parity], axis=0)

    # -- Reconstruct -------------------------------------------------------

    def reconstruct_data(
        self, shards: dict[int, np.ndarray] | list[np.ndarray | None]
    ) -> dict[int, jax.Array]:
        """Recompute all missing DATA shards from any k survivors.

        `shards`: dict shard_id -> [B] bytes, or list with None for missing.
        Returns {shard_id: [B] uint8} for every previously-missing data shard.
        """
        return self._reconstruct_fused(shards, self.data_shards)

    def reconstruct(
        self, shards: dict[int, np.ndarray] | list[np.ndarray | None]
    ) -> dict[int, jax.Array]:
        """Recompute ALL missing shards (data and parity) from any k
        survivors — one fused [missing, k] GF matmul, no second encode
        pass (fused_reconstruct_matrix)."""
        return self._reconstruct_fused(shards, self.total_shards)

    def _reconstruct_fused(self, shards, limit: int) -> dict[int, jax.Array]:
        present = self._as_dict(shards)
        missing = tuple(i for i in range(limit) if i not in present)
        if not missing:
            return {}
        pres = tuple(sorted(present.keys()))
        if not self.geometry.is_rs:
            pm = geom_stacked_matrix(self.geometry, pres, missing)
            key = ("gdecs", self.geometry.name, pres, missing)
            stacked = jnp.stack([jnp.asarray(present[i], jnp.uint8)
                                 for i in pres])
            out = _dispatch_matmul(pm, stacked, key=key)
            return {i: out[j] for j, i in enumerate(missing)}
        fmat, used = fused_reconstruct_matrix(
            self.data_shards, self.parity_shards, pres, missing)
        key = ("fdec", self.data_shards, self.parity_shards, pres, missing)
        stacked = jnp.stack([jnp.asarray(present[i], jnp.uint8) for i in used])
        out = _dispatch_matmul(fmat, stacked, key=key)
        return {i: out[j] for j, i in enumerate(missing)}

    def reconstruct_stacked(
        self, present_ids: tuple[int, ...],
        stacked: np.ndarray | jax.Array, data_only: bool = False,
        device=None, want: tuple[int, ...] | None = None,
    ) -> tuple[tuple[int, ...], jax.Array]:
        """Reconstruct from survivors already stacked [P, B] in caller
        row order -> (missing_ids, [len(missing), B]).

        The hot-path form: the rebuild pipeline reads survivor shards
        into ONE contiguous buffer, so re-stacking k device rows per
        batch (an extra ~2x HBM round-trip at rebuild sizes) is pure
        waste. Instead the fused [missing, k] matrix is column-permuted
        to the caller's row order, with zero columns for surplus
        survivors — identical GF math, zero data movement.

        `device` pins the dispatch to one chip: the scheduler's
        per-survivor-set chip placement routes every slab sharing this
        fused matrix to the chip where the matrix already lives."""
        limit = self.data_shards if data_only else self.total_shards
        present_ids = tuple(present_ids)
        if device is not None:
            # commit FIRST (see encode_parity): survivors go straight to
            # the survivor set's chip, no default-device detour
            stacked = jax.device_put(np.asarray(stacked, np.uint8), device)
        stacked = jnp.asarray(stacked, jnp.uint8)
        assert stacked.shape[0] == len(present_ids), stacked.shape
        if want is not None or not self.geometry.is_rs:
            # geometry-general / minimal-read form (ISSUE 11): solve only
            # the wanted rows — the survivor set may be smaller than k
            # (an LRC local group) as long as it spans them
            targets = geom_targets_for(self.geometry, present_ids,
                                       data_only, want)
            if not targets:
                return (), jnp.zeros((0, stacked.shape[1]), jnp.uint8)
            pm = geom_stacked_matrix(self.geometry, present_ids, targets)
            key = ("gdecs", self.geometry.name, present_ids, targets)
            out = _dispatch_matmul(pm, stacked, key=key, device=device)
            return targets, out
        missing, pm = fused_reconstruct_stacked_matrix(
            self.data_shards, self.parity_shards, present_ids, limit)
        if not missing:
            return (), jnp.zeros((0, stacked.shape[1]), jnp.uint8)
        key = ("fdecs", self.data_shards, self.parity_shards,
               present_ids, missing)
        out = _dispatch_matmul(pm, stacked, key=key, device=device)
        return missing, out

    def verify(self, shards: np.ndarray | jax.Array) -> bool:
        """True iff parity rows match the data rows."""
        shards = jnp.asarray(shards, dtype=jnp.uint8)
        parity = self.encode_parity(shards[: self.data_shards])
        return bool(jnp.array_equal(parity, shards[self.data_shards:]))

    def parity_probe(self, shards: np.ndarray | jax.Array) -> jax.Array:
        """Scalar 0 iff stored parity matches recomputed parity, else the
        max differing byte — single-device form of the mesh coder's
        ICI-collective probe (parallel/mesh.ShardedCoder.parity_probe),
        keeping the coder surface uniform across device counts."""
        shards = jnp.asarray(shards, dtype=jnp.uint8)
        assert shards.shape[0] == self.total_shards, shards.shape
        parity = self.encode_parity(shards[: self.data_shards])
        return jnp.max((parity ^ shards[self.data_shards:]).astype(jnp.int32))

    parity_checksum = parity_probe

    # ----------------------------------------------------------------------

    def _as_dict(self, shards) -> dict[int, np.ndarray]:
        if isinstance(shards, dict):
            return dict(shards)
        return {i: s for i, s in enumerate(shards) if s is not None}

    def __hash__(self):  # for lru_cache on methods
        return hash((self.data_shards, self.parity_shards,
                     self.geometry.name))

    def __eq__(self, other):
        return (
            isinstance(other, RSCodecJax)
            and self.data_shards == other.data_shards
            and self.parity_shards == other.parity_shards
            and self.geometry.name == other.geometry.name
        )
