"""Multi-chip EC compute: shard stripe batches over a jax.sharding.Mesh.

The reference scales EC work by fanning goroutines across volume servers
(shell/command_ec_encode.go:194-251 copies shards in parallel; each server
encodes serially). The TPU-native scaling axis is different: parity is a
per-byte-column GF(2^8) matmul, so a stripe batch `data[k, B]` can be split
along B across every chip in a mesh with ZERO cross-chip communication for
encode/reconstruct — the ICI is only needed for integrity collectives
(e.g. fleet-wide parity probes via pmax).

Mesh axes used here:

  * ``stripe`` — the byte-column axis of a stripe batch (pure data parallel).
  * the SAME axis doubles as the V (volume/slab) axis for the stacked
    variants (ISSUE 5): a stacked batch ``[V, k, B]`` can shard whole
    slabs across chips instead of splitting every slab's columns —
    per-chip dispatch queues fill independently, which is what a fleet
    of concurrent encodes needs (RapidRAID's pipelined distribution of
    coding work across nodes, arXiv:1207.6744).

`shard_map` gives each device its local [k, B/n] slab; the same bitsliced
MXU matmul from ops/rs_jax.py runs per-device. Outputs keep the same
sharding, so a host only pulls back the shard slabs it will write locally.

This module is also the ONE sanctioned device-enumeration point:
tools/lint.py rejects bare ``jax.devices()`` anywhere else — device
placement must go through the helpers here so mesh policy stays in one
file.
"""

from __future__ import annotations

import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.rs_jax import (
    fused_reconstruct_op,
    fused_reconstruct_stacked_op,
    geom_parity_op,
    geom_stacked_op,
    geom_targets_for,
    gf_matmul_bits,
    parity_matrix_op,
)

STRIPE_AXIS = "stripe"

# Widest byte-column slab one device takes per mesh dispatch; wider work
# runs as several dispatches, so the programs a mesh compiles stay at or
# below this width. The v5e compile fuses the bitsliced program with no
# scratch: about 20 B of HBM per column (data rows padded in, parity out),
# 168 MB a chip at 8 MiB (compile rehearsal, tests/test_tpu_compile.py).
# Columns are independent, so the bytes are identical.
MESH_DISPATCH_COLS = 8 << 20

# Serialized-submission guard (found by ISSUE 3's tier-1 CPU mesh): two
# threads concurrently submitting multi-device shard_map modules interleave
# XLA's cross-module rendezvous and deadlock. The lock covers SUBMISSION
# only — the returned arrays are async, so batches still pipeline
# device-side. The EC dispatch scheduler holds its own lock too; this one
# protects the direct-call paths (scheduler off, concurrent scrubbers).
_SUBMIT_MU = threading.Lock()


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def local_devices() -> list:
    """Every device this process can place work on — THE sanctioned
    enumeration call (see module docstring / tools/lint.py)."""
    return list(jax.devices())


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Where JAX_COMPILATION_CACHE_DIR is set, JAX
    reads it itself and nothing is overridden; otherwise the cache lives
    at the fixed `<repo>/.jax_cache` (git-ignored). The path is part of
    every entry's key, so it never comes from a temp name, pid or clock.
    Every program is kept, however fast it compiled: a server compiles
    one per dispatch width and survivor set. Entry points call this
    (command.main, chip_smoke.py, benchmark/harness.py); tests do
    not."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_count() -> int:
    """len(local_devices()) without making callers touch jax directly."""
    return len(local_devices())


def make_mesh(devices=None, axis: str = STRIPE_AXIS) -> Mesh:
    """1-D mesh over the given (default: all) devices."""
    if devices is None:
        devices = local_devices()
    return Mesh(np.asarray(devices), (axis,))


def _col_pad(b: int, n: int, quantum: int = 8) -> int:
    """Pad byte-columns so every device gets an equal, aligned slab."""
    step = n * quantum
    return (b + step - 1) // step * step


def _matrix_spec(matrix_op) -> P:
    return P(*(None,) * matrix_op.ndim)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _apply_sharded(matrix_op, data, mesh, axis):
    fn = jax.shard_map(
        gf_matmul_bits,
        mesh=mesh,
        in_specs=(_matrix_spec(matrix_op), P(None, axis)),
        out_specs=P(None, axis),
    )
    return fn(matrix_op, data)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _apply_stacked_vsharded(matrix_op, stack, mesh, axis):
    """stack [V, R, B] with V sharded across the mesh -> [V, out, B].

    Each device holds whole slabs ([V/n, R, B] locally) and runs ONE
    column-concatenated GF matmul over them — the V-axis counterpart of
    `_apply_sharded`'s byte-column split. Zero cross-chip communication,
    like the column form: slabs are independent."""
    def local(m, s):
        v, r, b = s.shape
        wide = jnp.swapaxes(s, 0, 1).reshape(r, v * b)
        out = gf_matmul_bits(m, wide)
        return jnp.swapaxes(out.reshape(out.shape[0], v, b), 0, 1)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(_matrix_spec(matrix_op), P(axis, None, None)),
        out_specs=P(axis, None, None),
    )
    return fn(matrix_op, stack)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _parity_probe(matrix_op, shards, mesh, axis, data_shards):
    """max over all bytes of (recomputed parity ^ stored parity); 0 iff clean.
    pmax over the mesh axis rides the ICI — cannot wrap, unlike a sum."""
    def local(m, x):
        par = gf_matmul_bits(m, x[:data_shards])
        diff = jnp.max((par ^ x[data_shards:]).astype(jnp.int32))
        return jax.lax.pmax(diff, axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(_matrix_spec(matrix_op), P(None, axis)),
        out_specs=P(),
    )(matrix_op, shards)


class ShardedCoder:
    """RS codec over a device mesh: same 4-call surface as RSCodecJax, with
    the byte axis sharded across `mesh` (encode/reconstruct are
    embarrassingly parallel across byte columns, SURVEY.md §5.7-5.8).
    """

    def __init__(self, data_shards: int = 10, parity_shards: int = 4,
                 mesh: Mesh | None = None, geometry=None):
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
        self.mesh = mesh if mesh is not None else make_mesh()
        self.axis = self.mesh.axis_names[0]
        self._n = self.mesh.devices.size
        self._parity_op = jnp.asarray(
            parity_matrix_op(data_shards, parity_shards)
            if self.geometry.is_rs
            else geom_parity_op(self.geometry)
        )

    @property
    def geometry_id(self) -> str:
        return self.geometry.name

    # -- sharding helpers --------------------------------------------------

    def _shard(self, data) -> tuple[jax.Array, int]:
        """Place [rows, B] on the mesh with columns sharded; pad B to the
        device quantum. Device-resident correctly-sharded input passes
        through without a host round-trip."""
        b = data.shape[1]
        padded = _col_pad(b, self._n)
        sharding = NamedSharding(self.mesh, P(None, self.axis))
        if isinstance(data, jax.Array) and padded == b and data.sharding == sharding:
            return data, b
        data = np.asarray(data, dtype=np.uint8)
        if padded != b:
            data = np.pad(data, ((0, 0), (0, padded - b)))
        return jax.device_put(data, sharding), b

    def _apply_cols(self, op, data) -> jax.Array:
        """op applied to [rows, B] with the columns split over the mesh,
        at most MESH_DISPATCH_COLS columns per device per dispatch
        -> [out, B]."""
        b = data.shape[1]
        step = MESH_DISPATCH_COLS * self._n
        if b > step:
            return jnp.concatenate(
                [self._apply_cols(op, data[:, i:i + step])
                 for i in range(0, b, step)], axis=1)
        arr, b = self._shard(data)
        with _SUBMIT_MU:
            out = _apply_sharded(op, arr, self.mesh, self.axis)
        return out[:, :b]

    def _apply_vstack(self, op, stack: np.ndarray) -> jax.Array:
        """op applied to every slab of [V, rows, B] with whole slabs
        sharded over the mesh -> [V, out, B]; one slab of at most
        MESH_DISPATCH_COLS columns per device per dispatch. With two or
        more slabs per device the v5e compile of the per-device
        swapaxes/reshape took minutes (252 s for 2 x 2 MiB, 554 s for
        2 x 4 MiB, against 2.6 s for 1 x 4 MiB: compile rehearsal of the
        word-packed program this module ran before the bitsliced one)."""
        v, _, b = stack.shape
        if b > MESH_DISPATCH_COLS:
            return jnp.concatenate(
                [self._apply_vstack(op, stack[:, :, i:i + MESH_DISPATCH_COLS])
                 for i in range(0, b, MESH_DISPATCH_COLS)], axis=2)
        if v > self._n:
            return jnp.concatenate(
                [self._apply_vstack(op, stack[i:i + self._n])
                 for i in range(0, v, self._n)], axis=0)
        arr, v0, b0 = self._vshard_put(stack)
        with _SUBMIT_MU:
            out = _apply_stacked_vsharded(op, arr, self.mesh, self.axis)
        return out[:v0, :, :b0]

    # -- codec surface -----------------------------------------------------

    def encode_parity(self, data) -> jax.Array:
        """data [k, B] -> parity [m, B]; columns computed mesh-parallel."""
        assert data.shape[0] == self.data_shards, data.shape
        return self._apply_cols(self._parity_op, data)

    def _vshard_wanted(self, v: int) -> bool:
        """V-axis sharding pays when every chip gets at least one whole
        slab; SWFS_EC_MESH_VSHARD=0 pins the ISSUE-3 column split."""
        if v < self._n or self._n <= 1:
            return False
        return os.environ.get("SWFS_EC_MESH_VSHARD", "1").lower() not in (
            "0", "false", "off")

    def _vshard_put(self, stack: np.ndarray) -> tuple[jax.Array, int, int]:
        """Zero-pad V to a device multiple (and B to 8 columns, the
        quantum `_col_pad` uses) and place slab-sharded. Zero
        slabs/columns encode and reconstruct to zero bytes and are sliced
        away, the same argument as the scheduler's ragged-tail column
        padding."""
        v, r, b = stack.shape
        pad_v = -(-v // self._n) * self._n
        pad_b = -(-b // 8) * 8
        if pad_v != v or pad_b != b:
            stack = np.pad(stack, ((0, pad_v - v), (0, 0), (0, pad_b - b)))
        sharding = NamedSharding(self.mesh, P(self.axis, None, None))
        return jax.device_put(stack, sharding), v, b

    def encode_parity_stacked(self, stack) -> jax.Array:
        """stack [V, k, B] -> parity [V, m, B]: the V slabs ride ONE
        mesh-sharded dispatch. With V >= chips (and SWFS_EC_MESH_VSHARD
        on) the V axis itself shards — each chip encodes whole slabs,
        so a big stacked batch fans out with zero cross-chip traffic;
        otherwise columns are laid side by side ([k, V*B]) and split, as
        in ISSUE 3. Both are per-byte-column GF matmuls, so per-slab
        bytes are identical to V separate encode_parity calls either
        way (pinned by tests/test_mesh_dispatch.py)."""
        stack = np.asarray(stack, dtype=np.uint8)
        assert stack.ndim == 3 and stack.shape[1] == self.data_shards, \
            stack.shape
        v, k, b = stack.shape
        if self._vshard_wanted(v):
            return self._apply_vstack(self._parity_op, stack)
        wide = np.ascontiguousarray(
            stack.transpose(1, 0, 2).reshape(k, v * b))
        parity = self.encode_parity(wide)
        return jnp.swapaxes(
            parity.reshape(self.parity_shards, v, b), 0, 1)

    def reconstruct_stacked_vsharded(self, present_ids, stack,
                                     data_only: bool = False, want=None):
        """Uniform-width survivor stacks [V, P, B] -> (missing_ids,
        [V, len(missing), B]) with the V axis sharded across chips —
        every chip reconstructs whole slabs through the same fused
        column-permuted matrix (same GF math as reconstruct_stacked,
        including the `want` minimal-read form, so bytes are identical
        slab for slab)."""
        present_ids = tuple(present_ids)
        stack = np.asarray(stack, dtype=np.uint8)
        assert stack.ndim == 3 and stack.shape[1] == len(present_ids), \
            stack.shape
        limit = self.data_shards if data_only else self.total_shards
        if want is not None or not self.geometry.is_rs:
            missing = geom_targets_for(self.geometry, present_ids,
                                       data_only, want)
            op_np = (geom_stacked_op(self.geometry, present_ids, missing)
                     if missing else None)
        else:
            missing, op_np = fused_reconstruct_stacked_op(
                self.data_shards, self.parity_shards, present_ids, limit)
        if not missing:
            return (), jnp.zeros(
                (stack.shape[0], 0, stack.shape[2]), jnp.uint8)
        if stack.shape[0] == 0:  # V=0: nothing to shard, shape contract
            return missing, jnp.zeros(
                (0, len(missing), stack.shape[2]), jnp.uint8)
        return missing, self._apply_vstack(jnp.asarray(op_np), stack)

    # -- per-chip (device-affine) entry points ------------------------------
    #
    # The EC dispatch scheduler's per-chip lanes (ops/dispatch.py) flush
    # each chip's queued slabs as ONE single-device stacked dispatch
    # pinned to that chip — no shard_map, no rendezvous, every chip's
    # dispatch queue fills independently.

    def placement_devices(self) -> list:
        """The mesh's devices, in mesh order — the chips the dispatch
        scheduler round-robins encode slabs (and pins survivor sets) to."""
        return list(self.mesh.devices.flat)

    @property
    def prefers_vstack(self) -> bool:
        """Tells the dispatch scheduler (ISSUE 12) to keep [V, k, B]
        stacks for this coder's non-chip lanes: a multi-device mesh
        shards WHOLE slabs across chips (V-axis, ISSUE 5), which the
        column-compact wide packing would flatten away."""
        return self._n > 1

    def _chip_codec(self):
        # lazily-built single-device codec reused for every chip: jit
        # caches per (shape, device), so chips don't trample each other
        impl = self.__dict__.get("_chip_impl")
        if impl is None:
            from ..ops.rs_jax import RSCodecJax

            impl = self.__dict__["_chip_impl"] = RSCodecJax(
                self.data_shards, self.parity_shards,
                geometry=self.geometry)
        return impl

    def encode_parity_stacked_on(self, stack, device) -> jax.Array:
        """stack [V, k, B] encoded in one stacked dispatch pinned to
        `device` (bytes identical to encode_parity_stacked — columns are
        independent of where they're computed)."""
        return self._chip_codec().encode_parity_stacked(stack,
                                                        device=device)

    def encode_parity_on(self, data, device) -> jax.Array:
        """Wide/2-D [k, W] encode pinned to `device` — the arena-packed
        chip-lane form (ISSUE 12): the scheduler lays a whole flush's
        slabs side by side along the column axis and this dispatches
        them as ONE launch with no stacked [V, k, B] copy at all. The
        committed input buffer is donated to XLA (rs_jax donation
        plumbing), so per-flush device scratch is the payload bytes."""
        return self._chip_codec().encode_parity(data, device=device)

    def reconstruct_stacked_on(self, present_ids, stacked,
                               data_only: bool = False, device=None,
                               want=None):
        """Pre-stacked survivors [P, B] reconstructed on `device`; the
        survivor set's fused decode matrix is cached device-resident
        (ops/rs_jax._op_on_device, LRU)."""
        return self._chip_codec().reconstruct_stacked(
            present_ids, stacked, data_only=data_only, device=device,
            want=want)

    def encode(self, shards) -> jax.Array:
        """[k, B] data or [total, B] shards -> all [total, B] shards with
        parity rows (re)computed."""
        shards = np.asarray(shards, dtype=np.uint8)
        assert shards.shape[0] in (self.data_shards, self.total_shards), shards.shape
        parity = self.encode_parity(shards[: self.data_shards])
        return jnp.concatenate(
            [jnp.asarray(shards[: self.data_shards]), parity], axis=0
        )

    def reconstruct(self, shards) -> dict[int, jax.Array]:
        return self._reconstruct(shards, data_only=False)

    def reconstruct_data(self, shards) -> dict[int, jax.Array]:
        return self._reconstruct(shards, data_only=True)

    def _reconstruct(self, shards, data_only: bool) -> dict[int, jax.Array]:
        present = (
            dict(shards)
            if isinstance(shards, dict)
            else {i: s for i, s in enumerate(shards) if s is not None}
        )
        limit = self.data_shards if data_only else self.total_shards
        missing = tuple(i for i in range(limit) if i not in present)
        if not missing:
            return {}
        if not self.geometry.is_rs:
            pres = tuple(sorted(present.keys()))
            op_np = geom_stacked_op(self.geometry, pres, missing)
            used = pres
        else:
            # one fused [missing, k] matmul — parity rows are folded
            # through the decode matrix host-side
            # (rs_jax.fused_reconstruct_matrix), so no second mesh-wide
            # encode dispatch
            op_np, used = fused_reconstruct_op(
                self.data_shards, self.parity_shards,
                tuple(sorted(present.keys())), missing)
        stacked = np.stack([np.asarray(present[i], np.uint8) for i in used])
        out_arr = self._apply_cols(jnp.asarray(op_np), stacked)
        return {i: out_arr[j] for j, i in enumerate(missing)}

    def reconstruct_stacked(self, present_ids, stacked,
                            data_only: bool = False, want=None):
        """Pre-stacked survivors [P, B] in caller row order ->
        (missing_ids, [missing, B]) — the column-permuted fused matmul
        sharded over the mesh, no re-stack/gather (same contract as
        RSCodecJax.reconstruct_stacked, including the ISSUE-11 `want`
        minimal-read form)."""
        present_ids = tuple(present_ids)
        assert stacked.shape[0] == len(present_ids), stacked.shape
        limit = self.data_shards if data_only else self.total_shards
        if want is not None or not self.geometry.is_rs:
            missing = geom_targets_for(self.geometry, present_ids,
                                       data_only, want)
            op_np = (geom_stacked_op(self.geometry, present_ids, missing)
                     if missing else None)
        else:
            missing, op_np = fused_reconstruct_stacked_op(
                self.data_shards, self.parity_shards, present_ids, limit)
        if not missing:
            return (), jnp.zeros((0, stacked.shape[1]), jnp.uint8)
        # hand the buffer to _shard untouched: a device-resident,
        # correctly-sharded array must keep its fast path (np.asarray
        # here would be a device->host->device round trip)
        return missing, self._apply_cols(jnp.asarray(op_np), stacked)

    def verify(self, shards) -> bool:
        shards = np.asarray(shards, dtype=np.uint8)
        return int(self.parity_probe(shards)) == 0

    # -- fleet integrity collective ---------------------------------------

    def parity_probe(self, shards) -> jax.Array:
        """Scalar 0 iff stored parity matches recomputed parity, else the max
        differing byte value — an all-chip integrity scrub using a pmax
        collective over ICI (analogue of volume.check.disk's replica digest
        comparison, SURVEY.md §5.3)."""
        shards = np.asarray(shards, dtype=np.uint8)
        assert shards.shape[0] == self.total_shards, shards.shape
        step = MESH_DISPATCH_COLS * self._n  # as in _apply_cols
        if shards.shape[1] > step:
            return jnp.max(jnp.stack(
                [self.parity_probe(shards[:, i:i + step])
                 for i in range(0, shards.shape[1], step)]))
        arr, _ = self._shard(shards)
        with _SUBMIT_MU:
            return _parity_probe(self._parity_op, arr, self.mesh,
                                 self.axis, self.data_shards)

    # kept as the historical name used by the dry-run driver
    parity_checksum = parity_probe
