"""Device-resident XOR parity — manufactured redundancy for sharded state
(the ICP analogue at tensor level; DESIGN.md §4.2 and the parity-rung
section).

One parity shard per covered state leaf (params AND optimizer state): for a
leaf split into D shards, ``parity = XOR_d shard_d`` (over the raw ``to_i32``
bits), so any single lost or corrupt shard is exactly reconstructible from
the surviving peers plus parity — ``shard_j = parity ^ XOR_{d != j} shard_d``
— with no host snapshot and no replay.  XOR is bit-exact, so the
exact-or-abort rule holds with no floating-point caveats.

Coordinate system (the satellite bugfix this module exists for): the shard
boundaries are derived from each leaf's actual ``NamedSharding`` slices
(``kernels.digest.shard_indices``, mesh-flat device order — the SAME map the
sharded canary's digest tables and ``host_shard_checksums`` use), so the
(leaf, shard) a ``FaultReport`` attributes and the parity block it indexes
are one coordinate system by construction.  The seed's ``_split``
(first-divisible-dim) could disagree with a TP-sharded layout; a slice-map
derivation cannot.  Off-mesh the "shards" are D equal row-aligned chunks of
the flat ``to_i32`` view — again used identically by build, update and
reconstruct.

Replication: a leaf that is only partially sharded (e.g. TP-sharded but
DP-replicated) maps several devices to the SAME logical slice.  XOR over
identical copies self-cancels (an even replica count contributes zero!),
so the stream is built over the leaf's UNIQUE logical blocks — the slice
map deduplicated in mesh-flat device order — with zero rows padding the
shard axis.  ``device_block[key]`` translates a device-coordinate shard id
(what the sharded canary attributes) into the unique-block coordinate this
module reconstructs in; a repair is placed back on EVERY device holding
the injured block, keeping replicas bit-consistent.

Layout: the per-leaf parity blocks are concatenated into ONE int32 buffer —

  * off-mesh: tile-shaped ``(nt, TILE_ROWS, LANES)`` so the hot-path update
    is a single Pallas launch (``kernels.parity.xor_update_tiles``, parity
    aliased in place).  Each leaf's ``old ^ new`` delta is folded over its
    D blocks before it is laid out, so the kernel's input is one
    parity-sized buffer, written in place, not the D-row stream;
  * on a mesh: ``(D, Crow)`` sharded ``P(axis_names, None)`` like the digest
    packing buffers — each device holds 1/D of the parity (total memory
    overhead = state_bytes/D).

Hard loss (``row_safe=True``; DESIGN.md §7): the default placement puts
parity row ``d`` on device ``d`` — a whole lost DATA ROW therefore takes
its parity down with its data, and a leaf sharded over both the data and
the model axis loses SEVERAL unique blocks at once (one per model
column), which a single flat XOR fold cannot reconstruct.  ``row_safe``
mode fixes both for the elastic remesh path:

  * **placement** — the buffer is sharded over the NON-batch axes only
    (``P(("model",), None)``; fully replicated on a pure-DP mesh), so
    every surviving data row holds a complete copy of the parity.  The
    per-device memory cost rises from stream/D to stream/tp.
  * **fold groups** — unique blocks are grouped by their slice projection
    onto the dims NOT sharded over batch axes; the XOR fold runs PER
    GROUP (the stream carries ``n_groups × block_len`` columns per leaf),
    so a lost data row erases at most ONE member of each group — exactly
    the single erasure XOR inverts.  Only data-sharded leaves are covered
    in this mode: replicated / model-only leaves keep a surviving replica
    on the remaining rows and are re-gathered instead (launch/elastic.py).

Host-side reconstruction (``host_parity_flat`` / ``host_surviving_blocks``
/ ``host_reconstruct_block`` / ``host_assemble_leaf``) reads ONLY shards
on surviving devices — the honesty contract of the simulated-loss drill:
dead devices still answer in a single-process simulation, so every read
on the remesh path filters ``addressable_shards`` explicitly.

The hot-path entry points (``update_leaves`` / ``rebuild_leaves``) are pure
and traceable: the canary embeds them INSIDE its fused check/arm programs
(core/detect.py) and the fused step factory inside the donated step itself
(core/fused_step.py), so parity maintenance adds ZERO launches and ZERO
syncs to the steady state.  Updates are gated on the in-launch fault flag —
a detected fault zeroes the delta, so the committed parity keeps describing
the last healthy certified state version (the version the canary's read
generation certifies, which is exactly what reconstruction must produce).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels import digest as kdigest
from repro.kernels import parity as pk
from repro.kernels import ref as kref
from repro.kernels.ops import leaf_key

LANES = pk.LANES
TILE_ROWS = pk.TILE_ROWS
TILE = TILE_ROWS * LANES

#: dtypes whose ``to_i32`` view is invertible (``from_i32`` restores the
#: exact bits).  int64/float64 views are lossy (truncated), so leaves of
#: those dtypes are NOT parity-covered — a fault there escalates past the
#: parity rung instead of risking a silent wrong-bits repair.
_INVERTIBLE = tuple(map(jnp.dtype, (
    jnp.int32, jnp.float32, jnp.uint32,
    jnp.bfloat16, jnp.float16, jnp.int16, jnp.uint16,
    jnp.int8, jnp.uint8)))


def _covered(key: str, dtype, shape=None) -> bool:
    """Parity coverage: params + optimizer state (everything but induction
    state, which Eq.(1) repairs for free — the ``iv`` block and the 0-d
    optimizer counters ``opt/t``/bias corrections) in invertible dtypes."""
    if shape is not None and tuple(shape) == ():
        return False
    return not key.startswith("iv") and jnp.dtype(dtype) in _INVERTIBLE


def _norm_slices(idx, shape) -> Tuple[Tuple[int, int], ...]:
    """devices_indices_map entry -> ((start, stop), ...) per dim."""
    out = []
    for sl, dim in zip(idx, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


class ParityPlan:
    """Block layout + traceable parity math for one (structure, sharding)
    pair.  Cached globally (``parity_plan_for``) so every store over the
    same structure — e.g. one per campaign trial — shares the layout and
    the compiled functions that close over it (no per-trial retraces)."""

    def __init__(self, keys: Tuple[str, ...],
                 shapes: Dict[str, Tuple[int, ...]],
                 dtypes: Dict[str, str],
                 slices: Optional[Dict[str, Tuple]],
                 n_shards: int, mesh=None,
                 groups: Optional[Dict[str, Tuple[Tuple[int, ...], ...]]]
                 = None,
                 row_safe: bool = False,
                 parity_axes: Tuple[str, ...] = ()):
        self.keys = keys
        self.key_set = frozenset(keys)
        self.shapes = shapes
        self.dtypes = dtypes
        #: key -> UNIQUE ((start, stop), ...) slice tuples in first-seen
        #: mesh-flat device order — mesh mode only (replicas deduplicated)
        self.slices = slices
        self.n_shards = n_shards
        self.mesh = mesh
        self.axis_names = tuple(mesh.axis_names) if mesh is not None else ()
        #: row-loss-survivable mode: fold per group, shard the buffer over
        #: the non-batch axes only (``parity_axes``; () -> replicated)
        self.row_safe = row_safe
        self.parity_axes = tuple(parity_axes)

        #: per-key common block length (int32 elements; blocks are padded
        #: to it so every leaf contributes equal columns to the stream)
        self.block_len: Dict[str, int] = {}
        #: per-key per-block true (unpadded) sizes and shapes
        self.block_sizes: Dict[str, Tuple[int, ...]] = {}
        self.block_shapes: Dict[str, Tuple[Tuple[int, ...], ...]] = {}
        #: per-key count of unique logical blocks (<= n_shards)
        self.n_blocks: Dict[str, int] = {}
        #: per-key device-coordinate shard id -> unique block id (mesh:
        #: the sharded canary attributes faults per DEVICE; off-mesh the
        #: two coordinate systems coincide)
        self.device_block: Dict[str, Tuple[int, ...]] = {}
        #: per-key fold groups: tuple of member-block-id tuples.  Default
        #: (non-row_safe) is ONE group holding every block — the original
        #: flat fold, same stream layout, bit for bit.
        self.groups: Dict[str, Tuple[Tuple[int, ...], ...]] = {}
        #: per-key block id -> (group, member index within the group)
        self.block_group: Dict[str, Tuple[Tuple[int, int], ...]] = {}
        self.n_groups: Dict[str, int] = {}
        off = 0
        self.offsets: Dict[str, int] = {}
        for k in keys:
            shape = shapes[k]
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            if slices is None:
                c = max(1, -(-size // n_shards))
                self.block_len[k] = c
                self.block_sizes[k] = tuple(
                    max(0, min(c, size - d * c)) for d in range(n_shards))
                self.block_shapes[k] = tuple(
                    (self.block_sizes[k][d],) for d in range(n_shards))
                self.n_blocks[k] = n_shards
                self.device_block[k] = tuple(range(n_shards))
            else:
                uniq, dev_to_blk = slices[k]
                bshapes = tuple(
                    tuple(stop - start for start, stop in idx)
                    for idx in uniq)
                bsizes = tuple(
                    int(np.prod(bs, dtype=np.int64)) if bs else 1
                    for bs in bshapes)
                self.block_shapes[k] = bshapes
                self.block_sizes[k] = bsizes
                self.block_len[k] = max(bsizes)
                self.n_blocks[k] = len(uniq)
                self.device_block[k] = dev_to_blk
            gk = (groups or {}).get(k)
            if gk is None:
                gk = (tuple(range(self.n_blocks[k])),)
            self.groups[k] = gk
            bg = [(0, 0)] * self.n_blocks[k]
            for g, members in enumerate(gk):
                for m, blk in enumerate(members):
                    bg[blk] = (g, m)
            self.block_group[k] = tuple(bg)
            self.n_groups[k] = len(gk)
            self.offsets[k] = off
            off += self.n_groups[k] * self.block_len[k]
        #: total parity stream length (int32 elements)
        self.stream_len = off
        if row_safe:
            self.fold_width = max(
                [1] + [max((len(g) for g in self.groups[k]), default=1)
                       for k in keys])
        else:
            self.fold_width = n_shards
        if mesh is None:
            self.n_tiles = max(1, -(-self.stream_len // TILE))
            self.buffer_shape = (self.n_tiles, TILE_ROWS, LANES)
            self.buffer_spec = None
        elif row_safe:
            rows = 1
            for a in self.parity_axes:
                rows *= mesh.shape[a]
            crow = max(LANES, -(-self.stream_len // rows))
            crow = -(-crow // LANES) * LANES
            self.buffer_shape = (rows, crow)
            self.buffer_spec = P(self.parity_axes if self.parity_axes
                                 else None, None)
        else:
            crow = max(LANES, -(-self.stream_len // n_shards))
            crow = -(-crow // LANES) * LANES
            self.buffer_shape = (n_shards, crow)
            self.buffer_spec = P(self.axis_names, None)
        self._recon_cache: Dict[Tuple[str, int], object] = {}

    # -- layout helpers ----------------------------------------------------

    @property
    def memory_bytes(self) -> int:
        return int(np.prod(self.buffer_shape, dtype=np.int64)) * 4

    def leaves(self, tree) -> List:
        """Covered leaves in plan-key order."""
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        by_key = {leaf_key(p): x for p, x in flat}
        return [by_key[k] for k in self.keys]

    def block_devices(self, key: str, blk: int) -> Tuple[int, ...]:
        """Mesh-flat device indices holding unique block ``blk`` — where a
        reconstructed block must be placed back (all replicas)."""
        return tuple(i for i, b in enumerate(self.device_block[key])
                     if b == blk)

    def make_buffer(self):
        """Zero parity buffer with the plan's device layout."""
        z = jnp.zeros(self.buffer_shape, jnp.int32)
        if self.mesh is not None:
            z = jax.device_put(
                z, NamedSharding(self.mesh, self.buffer_spec))
        return z

    # -- traceable stream construction ------------------------------------

    def _block_rows(self, key: str, leaf) -> List[jnp.ndarray]:
        """Per-unique-block padded int32 rows (mesh mode)."""
        c = self.block_len[key]
        uniq, _ = self.slices[key]
        rep = NamedSharding(self.mesh, P(None)) \
            if self.row_safe and self.mesh is not None else None
        rows = []
        for idx in uniq:
            blk = leaf[tuple(slice(a, b) for a, b in idx)]
            row = kref.to_i32(blk)
            if rep is not None:
                # jax 0.4.x XLA:CPU SPMD miscompiles concatenate over
                # flattened slices of a middle-dim-sharded operand (wrong
                # VALUES, not layout); pinning each row replicated before
                # any stack/concat keeps the downstream fold local.  The
                # gather is semantically free: the group fold XORs blocks
                # living on different data rows, so cross-row movement of
                # the stream is inherent to parity maintenance.
                row = jax.lax.with_sharding_constraint(row, rep)
            if row.shape[0] < c:
                row = jnp.pad(row, (0, c - row.shape[0]))
            rows.append(row)
        return rows

    def _leaf_blocks(self, key: str, leaf) -> jnp.ndarray:
        """(fold_width, n_groups*block_len) int32 — the leaf's unique
        logical blocks laid out for the fold, derived from the SAME slice
        map the canary's shard digests use (a replicated slice contributes
        ONCE; duplicate copies would self-cancel under XOR).  Row m holds
        each group's m-th member side by side; rows past a group's size
        are zero padding, so folding the row axis XORs exactly the members
        of each group into that group's parity segment."""
        c = self.block_len[key]
        if self.slices is None:
            flat = kref.to_i32(leaf)
            flat = jnp.pad(flat, (0, self.n_shards * c - flat.shape[0]))
            return flat.reshape(self.n_shards, c)
        rows = self._block_rows(key, leaf)
        if not self.row_safe:
            if len(rows) < self.fold_width:
                rows.append(jnp.zeros(
                    (self.fold_width - len(rows), c), jnp.int32))
                return jnp.concatenate(
                    [jnp.stack(rows[:-1]), rows[-1]], axis=0)
            return jnp.stack(rows)
        zero = jnp.zeros((c,), jnp.int32)
        out = []
        for m in range(self.fold_width):
            segs = [rows[members[m]] if m < len(members) else zero
                    for members in self.groups[key]]
            out.append(segs[0] if len(segs) == 1
                       else jnp.concatenate(segs))
        return jnp.stack(out)

    def stream_mat(self, leaves: Sequence) -> jnp.ndarray:
        """(fold_width, stream_len) int32: the fold input columns."""
        mat = jnp.concatenate(
            [self._leaf_blocks(k, leaf)
             for k, leaf in zip(self.keys, leaves)], axis=1)
        if self.mesh is not None and not self.row_safe:
            mat = jax.lax.with_sharding_constraint(
                mat, NamedSharding(self.mesh, P(self.axis_names, None)))
        return mat

    def _tiles(self, blocks: Sequence) -> jnp.ndarray:
        """(r, nt, TILE_ROWS, LANES) off-mesh kernel input from each key's
        (r, block_len) rows: every row written in place into one flat
        zeroed buffer, whose tile reshape is free, so the stream, its tail
        pad and its tile layout are one temporary, not three."""
        r = blocks[0].shape[0]
        n = self.n_tiles * TILE
        flat = jnp.zeros((r * n,), jnp.int32)
        for k, blk in zip(self.keys, blocks):
            for d in range(r):
                flat = jax.lax.dynamic_update_slice(
                    flat, blk[d], (d * n + self.offsets[k],))
        return flat.reshape(r, self.n_tiles, TILE_ROWS, LANES)

    def _fold_rows(self, mat: jnp.ndarray) -> jnp.ndarray:
        """XOR-reduce the shard axis and lay the fold out as the mesh
        parity buffer (D, Crow) sharded over the mesh."""
        # Unrolled elementwise XOR: XLA:CPU rejects a bitwise-xor
        # lax.reduce computation, and D is a small static constant anyway.
        fold = mat[0]
        for d in range(1, mat.shape[0]):
            fold = fold ^ mat[d]
        if self.row_safe:
            # pin the fold replicated BEFORE the buffer placement: the
            # partitioner otherwise propagates the buffer sharding back
            # through the fold and re-enters the miscompiled slice+concat
            # partitioning (see _block_rows) — the final constraint then
            # becomes a local slice-out of the replicated fold.
            fold = jax.lax.with_sharding_constraint(
                fold, NamedSharding(self.mesh, P(None)))
        pad = int(np.prod(self.buffer_shape, dtype=np.int64)) \
            - self.stream_len
        rows = jnp.pad(fold, (0, pad)).reshape(self.buffer_shape)
        return jax.lax.with_sharding_constraint(
            rows, NamedSharding(self.mesh, self.buffer_spec))

    # -- traceable hot-path entry points -----------------------------------

    def rebuild_leaves(self, leaves: Sequence) -> jnp.ndarray:
        """Parity from scratch — the donated-pair ``arm_current`` form
        (only one state version is ever visible under donation, so the
        per-step maintenance is a rebuild of the armed version)."""
        if not self.keys:
            # empty coverage (e.g. row_safe over a pure-DP state: every
            # leaf re-gathers from replicas instead) — keep a zero buffer
            z = jnp.zeros(self.buffer_shape, jnp.int32)
            if self.mesh is not None:
                z = jax.lax.with_sharding_constraint(
                    z, NamedSharding(self.mesh, self.buffer_spec))
            return z
        if self.mesh is not None:
            return self._fold_rows(self.stream_mat(leaves))
        return pk.xor_fold_tiles(self._tiles(
            [self._leaf_blocks(k, leaf)
             for k, leaf in zip(self.keys, leaves)]))

    def update_leaves(self, parity, old_leaves: Sequence,
                      new_leaves: Sequence, fault) -> jnp.ndarray:
        """Incremental update ``parity ^ XOR_d(old_d ^ new_d)``, gated:
        when ``fault`` (the launch's own mismatch flag) fires the delta is
        zeroed, so the committed parity keeps describing the last healthy
        version — the gate is applied to the DELTA, not the result, so the
        donated parity buffer is consumed exactly once (alias-safe)."""
        if not self.keys:
            return parity
        if self.mesh is not None:
            delta = self.stream_mat(old_leaves) ^ self.stream_mat(new_leaves)
            delta = jnp.where(fault, jnp.int32(0), delta)
            return parity ^ self._fold_rows(delta)
        # off-mesh, fold each leaf's delta over its D blocks before laying
        # it out: the kernel's input is one parity's size, not D of them
        folded = []
        for k, a, b in zip(self.keys, old_leaves, new_leaves):
            delta = self._leaf_blocks(k, a) ^ self._leaf_blocks(k, b)
            fold = delta[0]
            for d in range(1, delta.shape[0]):
                fold = fold ^ delta[d]
            folded.append(jnp.where(fault, jnp.int32(0), fold)[None])
        return pk.xor_update_tiles(self._tiles(folded), parity)

    # -- fault path: reconstruction ---------------------------------------

    def _parity_segment(self, parity, key: str,
                        group: int = 0) -> jnp.ndarray:
        off = self.offsets[key] + group * self.block_len[key]
        flat = parity.reshape(-1)
        return jax.lax.dynamic_slice(flat, (off,), (self.block_len[key],))

    def _survivor_fold(self, parity, leaf, key: str, shard: int):
        """group_parity_segment ^ XOR over the group's surviving members —
        the injured block's exact bits (padded to block_len).  ``shard``
        is a unique-block id; only its fold group participates (in the
        default single-group layout that is every block, the original
        flat fold)."""
        g, _ = self.block_group[key][shard]
        acc = self._parity_segment(parity, key, g)
        if self.slices is None:
            blocks = self._leaf_blocks(key, leaf)
            for d in range(self.n_blocks[key]):
                if d != shard:
                    acc = acc ^ blocks[d]
            return acc
        rows = self._block_rows(key, leaf)
        for blk in self.groups[key][g]:
            if blk != shard:
                acc = acc ^ rows[blk]
        return acc

    def reconstruct_shard(self, key: str, shard: int):
        """Compiled ``(parity, leaf) -> injured block`` (block shape, leaf
        dtype) for a mesh leaf — cached per (key, shard), fault path only."""
        ent = self._recon_cache.get((key, shard))
        if ent is None:
            bshape = self.block_shapes[key][shard]
            bsize = self.block_sizes[key][shard]
            dtype = self.dtypes[key]

            def recon(parity, leaf):
                acc = self._survivor_fold(parity, leaf, key, shard)
                return kref.from_i32(acc[:bsize], jnp.zeros(bshape, dtype))

            ent = jax.jit(recon)
            self._recon_cache[(key, shard)] = ent
        return ent

    def reconstruct_leaf(self, key: str, shard: int):
        """Compiled ``(parity, leaf) -> repaired whole leaf`` (off-mesh:
        the injured flat chunk is spliced back into the leaf's i32 view)."""
        ent = self._recon_cache.get((key, shard))
        if ent is None:
            c = self.block_len[key]
            bsize = self.block_sizes[key][shard]
            start = shard * c

            def recon(parity, leaf):
                acc = self._survivor_fold(parity, leaf, key, shard)
                flat = kref.to_i32(leaf)
                flat = jax.lax.dynamic_update_slice(
                    flat, acc[:bsize], (start,))
                return kref.from_i32(flat, leaf)

            ent = jax.jit(recon)
            self._recon_cache[(key, shard)] = ent
        return ent

    # -- hard-loss path: host-side, survivor-only reads --------------------
    #
    # The elastic remesh path (launch/elastic.py) runs on the HOST against
    # a mesh whose devices are partly "dead".  In the single-process
    # simulation dead devices still answer, so these helpers take the dead
    # device set explicitly and filter every ``addressable_shards`` read —
    # reading a dead shard would be cheating the drill.

    def _flat_device_index(self) -> Dict:
        devs = kdigest.mesh_device_order(self.mesh)
        return {dev: i for i, dev in enumerate(devs)}

    def host_parity_flat(self, parity, dead=frozenset()) -> np.ndarray:
        """The full flat parity stream assembled from SURVIVING devices
        only.  Raises if any parity region went down with the dead set —
        the row_safe placement exists precisely so it never does for a
        data-row loss."""
        if self.mesh is None:
            return np.asarray(parity).reshape(-1)[:self.stream_len]
        dead = set(dead)
        out = np.zeros(self.buffer_shape, np.int32)
        have = np.zeros(self.buffer_shape, bool)
        for sh in parity.addressable_shards:
            if sh.device in dead:
                continue
            out[sh.index] = np.asarray(sh.data)
            have[sh.index] = True
        if not bool(have.all()):
            raise RuntimeError(
                "parity rows lost along with the dead devices — a hard "
                "row loss needs the row_safe placement (ParityStore("
                "row_safe=True))")
        return out.reshape(-1)[:self.stream_len]

    def host_surviving_blocks(self, key: str, leaf,
                              dead=frozenset()) -> Dict[int, np.ndarray]:
        """block id -> padded int32 row, read only from surviving
        replicas (first surviving holder per unique block wins)."""
        c = self.block_len[key]
        fidx = self._flat_device_index()
        dmap = self.device_block[key]
        dead = set(dead)
        out: Dict[int, np.ndarray] = {}
        for sh in leaf.addressable_shards:
            if sh.device in dead:
                continue
            b = dmap[fidx[sh.device]]
            if b in out:
                continue
            row = np.asarray(kref.to_i32(sh.data))
            if row.shape[0] < c:
                row = np.pad(row, (0, c - row.shape[0]))
            out[b] = row
        return out

    def host_reconstruct_block(self, key: str, blk: int,
                               parity_flat: np.ndarray,
                               blocks: Dict[int, np.ndarray]) -> np.ndarray:
        """Lost block ``blk`` from its group's parity segment + surviving
        members — exact by XOR algebra.  Raises on a double erasure
        within the fold group (two dead members: not invertible)."""
        g, _ = self.block_group[key][blk]
        c = self.block_len[key]
        off = self.offsets[key] + g * c
        acc = parity_flat[off:off + c].astype(np.int32).copy()
        for other in self.groups[key][g]:
            if other == blk:
                continue
            row = blocks.get(other)
            if row is None:
                raise RuntimeError(
                    f"double erasure in the fold group of {key}: blocks "
                    f"{blk} and {other} are both lost — XOR parity "
                    f"inverts a single erasure per group")
            acc ^= row
        bsize = self.block_sizes[key][blk]
        bshape = self.block_shapes[key][blk]
        return np.asarray(kref.from_i32(
            jnp.asarray(acc[:bsize]),
            jnp.zeros(bshape, self.dtypes[key])))

    def host_assemble_leaf(self, key: str, leaf, dead=frozenset()):
        """(full host array, missing unique-block ids): surviving shards
        placed at their slice-map positions, blocks with no surviving
        replica listed for parity reconstruction."""
        fidx = self._flat_device_index()
        dmap = self.device_block[key]
        uniq, _ = self.slices[key]
        dead = set(dead)
        out = np.zeros(self.shapes[key], jnp.dtype(self.dtypes[key]))
        have = set()
        for sh in leaf.addressable_shards:
            if sh.device in dead:
                continue
            b = dmap[fidx[sh.device]]
            if b in have:
                continue
            out[tuple(slice(a, bnd) for a, bnd in uniq[b])] = \
                np.asarray(sh.data)
            have.add(b)
        missing = [b for b in range(self.n_blocks[key]) if b not in have]
        return out, missing


_PARITY_PLAN_CACHE: Dict[Tuple, ParityPlan] = {}


def evict_mesh_plans(mesh) -> int:
    """Drop cached ParityPlans keyed on ``mesh`` (elastic remesh: plans
    for the lost mesh must not pin dead-device layouts in memory)."""
    mk = kdigest._mesh_key(mesh)
    stale = [k for k in _PARITY_PLAN_CACHE if k[0] == mk]
    for k in stale:
        del _PARITY_PLAN_CACHE[k]
    return len(stale)


def _dim_axes(entry) -> Tuple[str, ...]:
    """PartitionSpec dim entry -> tuple of mesh axis names."""
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def parity_plan_for(tree, *, mesh=None, n_shards: int = 4,
                    row_safe: bool = False,
                    batch_axes: Tuple[str, ...] = ()) -> ParityPlan:
    """The cached ParityPlan for ``tree``'s structure (and, on a mesh, its
    actual NamedSharding layout — the slice map IS the plan).

    ``row_safe`` (requires ``mesh`` + ``batch_axes``): row-loss-survivable
    coverage — only DATA-sharded leaves are covered (replicated /
    model-only leaves keep surviving replicas and are re-gathered on the
    elastic path instead), blocks fold per group (grouped by their slice
    projection onto the non-data dims, so a lost row erases at most one
    member per group), and the buffer shards over the non-batch axes
    only.  Leaves with a dim sharded JOINTLY over batch and non-batch
    axes are excluded: a row loss would doubly erase inside one group
    (real model specs from ``spec_for_param`` never joint-shard)."""
    if row_safe and mesh is None:
        raise ValueError("row_safe parity requires a mesh")
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    bset = set(batch_axes)
    entries = []
    groups: Dict[str, Tuple[Tuple[int, ...], ...]] = {}
    for path, x in flat:
        k = leaf_key(path)
        dt = jnp.result_type(x)
        if not _covered(k, dt, jnp.shape(x)):
            continue
        shape = tuple(jnp.shape(x))
        gk = None
        if mesh is not None:
            sharding = getattr(x, "sharding", None)
            if not isinstance(sharding, NamedSharding):
                raise ValueError(
                    f"parity on a mesh requires NamedSharding leaves; "
                    f"{k} has {type(sharding).__name__}")
            if row_safe:
                spec = tuple(sharding.spec)
                spec = spec + (None,) * (len(shape) - len(spec))
                per_dim = [set(_dim_axes(e)) for e in spec]
                data_dims = tuple(i for i, ax in enumerate(per_dim)
                                  if ax and ax <= bset)
                mixed = any(ax & bset and ax - bset for ax in per_dim)
                if not data_dims or mixed:
                    continue
            per_dev = tuple(_norm_slices(idx, shape)
                            for idx in kdigest.shard_indices(x))
            # dedupe replicas in mesh-flat device order: XOR over
            # identical copies self-cancels, so the stream carries each
            # logical slice once; the device->block map rides along for
            # fault-attribution translation
            uniq: List[Tuple] = []
            seen: Dict[Tuple, int] = {}
            dev_to_blk = []
            for idx in per_dev:
                b = seen.get(idx)
                if b is None:
                    b = seen[idx] = len(uniq)
                    uniq.append(idx)
                dev_to_blk.append(b)
            sl = (tuple(uniq), tuple(dev_to_blk))
            if row_safe:
                # fold groups: same non-data projection -> same group
                # (members differ only in data coordinates, so one lost
                # row kills at most one member per group)
                dset = set(data_dims)
                gmap: Dict[Tuple, int] = {}
                glist: List[List[int]] = []
                for b, idx in enumerate(uniq):
                    p = tuple(s for i, s in enumerate(idx)
                              if i not in dset)
                    gi = gmap.get(p)
                    if gi is None:
                        gi = gmap[p] = len(glist)
                        glist.append([])
                    glist[gi].append(b)
                gk = tuple(tuple(g) for g in glist)
        else:
            sl = None
        entries.append((k, shape, dt.name, sl, gk))
        if gk is not None:
            groups[k] = gk
    entries.sort(key=lambda e: e[0])
    d = mesh.size if mesh is not None else max(2, n_shards)
    key = (kdigest._mesh_key(mesh) if mesh is not None else ("host", d),
           treedef, tuple(entries), row_safe, tuple(batch_axes))
    plan = _PARITY_PLAN_CACHE.get(key)
    if plan is None:
        if row_safe:
            parity_axes = tuple(a for a in mesh.axis_names
                                if a not in bset)
        else:
            parity_axes = ()
        plan = ParityPlan(
            keys=tuple(e[0] for e in entries),
            shapes={e[0]: e[1] for e in entries},
            dtypes={e[0]: e[2] for e in entries},
            slices={e[0]: e[3] for e in entries}
            if mesh is not None else None,
            n_shards=d, mesh=mesh,
            groups=groups if row_safe else None,
            row_safe=row_safe, parity_axes=parity_axes)
        _PARITY_PLAN_CACHE[key] = plan
    return plan


class ParityStore:
    """The live parity shard: one device-resident buffer + a version.

    Hot-path maintenance does NOT go through this object — the canary /
    fused step embed ``plan.update_leaves`` / ``plan.rebuild_leaves`` in
    their own launches and hand the donated-through buffer back to
    ``commit``.  The store's own methods are the off-hot-path half:
    ``build``/``rebuild`` after init or recovery, ``reconstruct_*`` on the
    fault path.
    """

    def __init__(self, tree, *, ctx=None, n_shards: int = 4,
                 row_safe: bool = False):
        mesh = ctx.mesh if (ctx is not None
                            and getattr(ctx, "enabled", False)) else None
        if row_safe and mesh is None:
            row_safe = False  # off-mesh: no rows to lose
        self.plan = parity_plan_for(
            tree, mesh=mesh, n_shards=n_shards, row_safe=row_safe,
            batch_axes=tuple(ctx.batch_axes) if row_safe else ())
        self.parity = self.plan.make_buffer()
        self.version = -1

    # -- coverage ---------------------------------------------------------

    def covers(self, key: str) -> bool:
        return key in self.plan.key_set

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards

    @property
    def memory_bytes(self) -> int:
        return self.plan.memory_bytes

    # -- off-hot-path maintenance -----------------------------------------

    def build(self, tree, step: int = 0) -> None:
        """(Re)build parity from scratch — init and post-recovery (a
        replayed/restored state is a new version; stale parity must not
        survive it).  One jitted call, off the hot path."""
        plan = self.plan
        fn = getattr(plan, "_rebuild_jit", None)
        if fn is None:
            fn = plan._rebuild_jit = jax.jit(plan.rebuild_leaves)
        self.parity = fn(plan.leaves(tree))
        self.version = step

    rebuild = build

    def commit(self, new_parity, step: int) -> None:
        """Install the buffer a hot-path launch donated through."""
        self.parity = new_parity
        self.version = step

    # -- fault path -------------------------------------------------------

    def reconstruct_shard(self, leaf, key: str, shard: int):
        """Injured mesh shard's exact bits (block shape, leaf dtype)."""
        return self.plan.reconstruct_shard(key, shard)(self.parity, leaf)

    def reconstruct_leaf(self, leaf, key: str, shard: int):
        """Off-mesh: the leaf with the injured chunk reconstructed."""
        return self.plan.reconstruct_leaf(key, shard)(self.parity, leaf)

    def scrub(self, tree, refs: Dict[str, np.ndarray]):
        """At-rest verify-and-repair sweep (the serving-side use: params
        never change while serving, so one parity build at load time plus
        this sweep detects AND repairs silent at-rest corruption with no
        reload and no model re-shard).

        ``refs`` holds the healthy digests recorded at build time —
        per-shard rows (``host_shard_checksums``) on a mesh, one
        whole-leaf ``host_checksum`` pair off-mesh.  Returns
        ``(repaired_tree, stats)``; leaves whose reconstruction does not
        digest-certify are reported in ``stats['failed']`` and left
        untouched (exact-or-abort — the caller escalates to a reload).
        """
        plan = self.plan
        on_mesh = plan.mesh is not None
        stats = {"checked": 0, "repaired": 0, "bytes_moved": 0,
                 "failed": []}
        repaired: Dict[str, object] = {}
        for key, leaf in zip(plan.keys, plan.leaves(tree)):
            ref = refs.get(key)
            if ref is None:
                continue
            stats["checked"] += 1
            ref = np.asarray(ref)
            if on_mesh:
                got = kdigest.host_shard_checksums(leaf)
                bad = np.nonzero(np.any(got != ref, axis=-1))[0]
                if not len(bad):
                    continue
                blocks = sorted({plan.device_block[key][int(i)]
                                 for i in bad})
                if len(blocks) > 1:
                    stats["failed"].append(key)
                    continue
                blk = blocks[0]
                block = np.asarray(self.reconstruct_shard(leaf, key, blk))
                holders = set(plan.block_devices(key, blk))
                devs = kdigest.mesh_device_order(leaf.sharding.mesh)
                by_dev = {sh.device: sh.data
                          for sh in leaf.addressable_shards}
                bufs = [jax.device_put(block, dev) if i in holders
                        else by_dev[dev] for i, dev in enumerate(devs)]
                new_leaf = jax.make_array_from_single_device_arrays(
                    leaf.shape, leaf.sharding, bufs)
                if not np.array_equal(
                        np.asarray(kdigest.host_shard_checksums(new_leaf)),
                        ref):
                    stats["failed"].append(key)
                    continue
                stats["bytes_moved"] += block.nbytes * len(holders)
            else:
                if np.array_equal(
                        np.asarray(kdigest.host_checksum(np.asarray(leaf))),
                        ref):
                    continue
                new_leaf = None
                for d in range(plan.n_blocks[key]):
                    cand = self.reconstruct_leaf(leaf, key, d)
                    if np.array_equal(
                            np.asarray(
                                kdigest.host_checksum(np.asarray(cand))),
                            ref):
                        new_leaf = cand
                        stats["bytes_moved"] += 4 * plan.block_sizes[key][d]
                        break
                if new_leaf is None:
                    stats["failed"].append(key)
                    continue
            repaired[key] = new_leaf
            stats["repaired"] += 1
        if not repaired:
            return tree, stats
        out = jax.tree_util.tree_map_with_path(
            lambda p, x: repaired.get(leaf_key(p), x), tree)
        return out, stats
