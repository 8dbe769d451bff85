"""Fused single-launch state digesting — the DigestPlan engine.

The paper's headline economics (~0% no-fault overhead) require detection to
cost one HBM-bandwidth streaming pass.  The seed implementation dispatched
one jit'd ``checksum`` per pytree leaf and forced a device→host sync per
leaf per step — O(leaves) kernel launches and blocking transfers on the
no-fault hot path.  This module replaces that with (DESIGN.md §4.2):

* **DigestPlan** — computed once per state *structure* (treedef + leaf
  shapes/dtypes) and cached: a flat int32 packing layout where every leaf
  occupies a private, TILE-aligned (32768-element / 128 KiB) range of a
  single buffer, so every tile belongs to exactly one leaf.  Alignment
  costs at most one tile per leaf (11 MiB for the 88 leaves of a K=1
  check-and-arm union) and buys a combine with no per-row tables.
* **one Pallas launch** per digest: all selected leaves are packed into
  one (nt, TILE_ROWS, LANES) buffer and digested by a single
  ``tile_checksums`` pallas_call, whose lane-dense (2, nt, LANES) output
  ``checksum.combine`` folds over each leaf's static tile range in one
  masked reduction (int32 wraparound arithmetic, so the result is
  bit-identical to per-leaf ``ops.checksum``).
* **device-side comparison** — consumers keep an on-device reference
  digest table (n_leaves, 2) and compare tables on device, fetching one
  scalar "any mismatch?" flag per check.  Leaf attribution via the
  leaf-index→path map happens only on the slow (fault) path.
* **persistent packing buffer** — each (plan, leaf-subset) owns ONE
  packing buffer for the lifetime of the plan.  The pack step writes each
  leaf's range with ``lax.dynamic_update_slice`` at its static offset and
  every jitted digest donates the buffer back into itself, so XLA updates
  it in place and a steady-state digest makes zero new device
  allocations: the same HBM range is rewritten every step (donation-safe
  hot path; DESIGN.md §4.2).  The writes stream HBM to HBM at any state
  size; a whole-buffer Pallas pack kernel would need the buffer in VMEM.
* **host digest path** — ``host_checksum``/``host_tree_checksums`` compute
  the same Fletcher digests in numpy uint32 wraparound arithmetic,
  bit-identical to the kernel: the oracle the device digests are tested
  against, and the certificate of mesh micro-snapshots (per leaf and per
  shard of the host copy).  Single-device micro-snapshots are certified
  on the device instead, through ``digest_subset`` over leaf groups
  (``core/microcheckpoint.py``).
* **digest as traceable subcomputation** — ``DigestPlan.digest_fn`` and
  ``check_arm_subcomputation`` return PURE functions whose only host-side
  work (plan lookup, tile ranges) happens at build/trace time: the
  traced path carries no dict lookups, so callers can embed a digest
  inside their own jitted program.  ``core/fused_step.py`` uses this to
  run the canary check+arm INSIDE the jitted (donated) training step.

Launch/sync/byte contract per detection mode, for state of ``B`` bytes,
canary period ``K`` and mesh size ``D`` (the DESIGN.md §4.2/§5 cost
table in code form; D=1 off-mesh):

  ===================  ========  =============  ==================
  mode                 launches  host syncs     bytes/step
  ===================  ========  =============  ==================
  per-leaf (seed)      O(L/K)    O(L/K)         ~2B/K
  fused check_and_arm  1         1 scalar       ~2B/K
  donated pair         2         1 scalar       ~2B/K
  in-step fused        0 extra*  1 scalar       ~2B/K
  sharded (any mode)   same      same 1 scalar  ~2B/K (÷D per dev)
  ===================  ========  =============  ==================

  *the in-step fused mode rides the step's own launch: the digest is a
  subcomputation of the jitted step (``core/fused_step.py``), so the
  no-fault hot path is 1 combined launch/step total — counted as one
  ``STATS.launches`` — at the cost of K rotation-specialised step
  executables.

Mesh sharding (``ShardedDigestPlan``/``sharded_plan_for``; DESIGN.md §5)
changes the *placement* of the work, not the contract: under shard_map
every device packs and digests only its addressable shard blocks against
its own slice of the sharded (n_shards, L, 2) reference tables, and the
single scalar the host fetches is the all-reduced any(fault) flag — the
only cross-device communication on the no-fault path.  Shard digests are
bit-identical to the single-device ``host_checksum`` oracle applied to
each shard's bytes (``host_shard_checksums``).

Instrumentation: ``STATS`` counts launches (one per digest invocation —
each digest is one jitted program: the in-place pack + one
``tile_checksums`` pallas_call, counted as a single fused launch; the
in-step fused mode counts its one combined step+digest dispatch), host
syncs (every device→host fetch in this module and in the canary goes
through ``fetch``), and traces
(incremented inside traced bodies, so a plan-cache hit provably does not
retrace).  The host digest path touches no device and counts nothing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import checksum as _ck
from repro.kernels import ref as _ref

LANES = _ck.LANES
TILE_ROWS = _ck.TILE_ROWS
TILE = _ck.TILE


# ---------------------------------------------------------------------------
# instrumentation
# ---------------------------------------------------------------------------

@dataclass
class DigestStats:
    """Hot-path accounting for the detection-cost model (DESIGN.md §4.2)."""
    launches: int = 0   # fused digest invocations (== pallas launches)
    syncs: int = 0      # device→host transfers
    traces: int = 0     # jit traces of digest functions (cache misses)

    def reset(self) -> None:
        self.launches = self.syncs = self.traces = 0

    def snapshot(self) -> Tuple[int, int, int]:
        return (self.launches, self.syncs, self.traces)


STATS = DigestStats()


def fetch(x) -> np.ndarray:
    """The ONLY device→host crossing in the digest subsystem — counted."""
    STATS.syncs += 1
    return np.asarray(x)


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

def leaf_key(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


@dataclass(frozen=True)
class LeafSpec:
    key: str
    index: int          # position in the plan's canonical (sorted-key) order
    size: int           # int32 elements (== element count; to_i32 is 1:1)
    n_rows: int         # tile-aligned: TILE_ROWS·max(1, ceil(size/TILE))

    @property
    def n_tiles(self) -> int:
        return self.n_rows // TILE_ROWS


class DigestPlan:
    """Packing layout + compiled digest functions for one state structure.

    The canonical leaf order is sorted-by-path (stable across runs and
    matching the rotating-canary slice assignment).  Every compiled
    function in a plan contains exactly one pallas_call.
    """

    def __init__(self, treedef, keys: Tuple[str, ...],
                 sizes: Tuple[int, ...]):
        self.treedef = treedef
        self.keys = keys                       # sorted
        self.specs = tuple(
            LeafSpec(key=k, index=i, size=s,
                     n_rows=TILE_ROWS * max(1, -(-s // TILE)))
            for i, (k, s) in enumerate(zip(keys, sizes)))
        self.n_leaves = len(keys)
        self.n_rows = sum(sp.n_rows for sp in self.specs)
        self.n_tiles = -(-self.n_rows // TILE_ROWS)
        self.bytes_per_pass = self.n_tiles * TILE_ROWS * LANES * 4
        self._key_to_index = {k: i for i, k in enumerate(keys)}
        self._digest_fns: Dict[Tuple[int, ...], object] = {}
        # donation-safe steady state: one jitted (donating) digest and one
        # persistent packing buffer per leaf subset
        self._jitted_fns: Dict[Tuple[int, ...], object] = {}
        self._pack_bufs: Dict[Tuple[int, ...], jnp.ndarray] = {}
        # permutation from tree_flatten_with_path order -> sorted-key order
        self._order: Optional[List[int]] = None

    # -- leaf extraction ---------------------------------------------------

    def leaves(self, tree) -> List:
        """Tree leaves in the plan's canonical (sorted-key) order.

        Rejects trees whose structure differs from the plan's — a renamed
        or moved leaf must never be silently digested against another
        leaf's reference row."""
        flat, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(
                f"tree structure does not match DigestPlan: got {treedef}, "
                f"plan was built for {self.treedef}")
        if self._order is None:
            with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
            paths = [leaf_key(p) for p, _ in with_path]
            self._order = sorted(range(len(paths)), key=lambda i: paths[i])
        return [flat[i] for i in self._order]

    def index_of(self, key: str) -> int:
        return self._key_to_index[key]

    # -- compiled digest over a leaf subset --------------------------------

    def digest_fn(self, indices: Optional[Sequence[int]] = None):
        """Traced digest core ``(pack_buf, leaves_subset) -> (pack_buf,
        (len(indices), 2) int32 table)``.

        ``indices`` selects plan leaves (canonical order); None = all.
        The returned function is pure/traceable: callers embed it in their
        own jit and donate the packing buffer at THEIR jit boundary (the
        canary does; ``digest_table`` below wraps it for direct use).
        Cached per subset, so the hot path never retraces.
        """
        idx = tuple(range(self.n_leaves)) if indices is None \
            else tuple(indices)
        fn = self._digest_fns.get(idx)
        if fn is None:
            fn = self._build_digest_fn(idx)
            self._digest_fns[idx] = fn
        return fn

    def _build_digest_fn(self, idx: Tuple[int, ...]):
        # leaf j owns tiles [first[j], stop[j]) of the buffer; fill tiles
        # and each leaf's tail stay all-zero for the life of the
        # persistent buffer, so they add nothing to any digest
        bounds = np.cumsum([0] + [self.specs[i].n_tiles for i in idx])
        first, stop = bounds[:-1], bounds[1:]
        nt = int(bounds[-1])

        def digest(buf, leaves):
            STATS.traces += 1          # trace-time only: counts cache misses
            # in-place tile-aligned packing into the persistent buffer:
            # only the leaf ranges are written, and caller donation makes
            # the writes allocation-free in steady state
            for leaf, t0 in zip(leaves, first):
                buf = jax.lax.dynamic_update_slice(
                    buf, _ref.to_i32(leaf), (int(t0) * TILE,))
            partials = _ck.tile_checksums(buf.reshape(nt, TILE_ROWS, LANES))
            return buf, _ck.combine(partials, first, stop)

        return digest

    # -- persistent packing buffers ----------------------------------------

    def take_buffer(self, indices: Optional[Sequence[int]] = None
                    ) -> jnp.ndarray:
        """The subset's packing buffer, to be donated into a digest call;
        pair with ``put_buffer`` on the returned alias.  A take/put pair
        REGISTERS the subset as hot-path-persistent (the canary's
        rotating slices); subsets digested via ``digest_table``/
        ``digest_subset`` without prior registration stay transient, so
        off-hot-path full-state digests do not pin packed-state HBM."""
        idx = tuple(range(self.n_leaves)) if indices is None \
            else tuple(indices)
        buf = self._pack_bufs.get(idx)
        if buf is None or buf.is_deleted():
            n_rows = sum(self.specs[i].n_rows for i in idx)
            padded = -(-n_rows // TILE_ROWS) * TILE_ROWS * LANES
            buf = jnp.zeros((padded,), jnp.int32)
            self._pack_bufs[idx] = buf
        return buf

    def put_buffer(self, indices: Optional[Sequence[int]],
                   buf: jnp.ndarray) -> None:
        """Store the donated-through buffer back as the subset's live one."""
        idx = tuple(range(self.n_leaves)) if indices is None \
            else tuple(indices)
        self._pack_bufs[idx] = buf

    def buffer_pointer(self, indices: Optional[Sequence[int]] = None):
        """Device address of the subset's packing buffer (None before first
        use) — the benchmark's steady-state buffer-reuse probe."""
        idx = tuple(range(self.n_leaves)) if indices is None \
            else tuple(indices)
        buf = self._pack_bufs.get(idx)
        return None if buf is None else buf.unsafe_buffer_pointer()

    def _jitted_digest(self, idx: Tuple[int, ...]):
        fn = self._jitted_fns.get(idx)
        if fn is None:
            fn = jax.jit(self.digest_fn(idx), donate_argnums=(0,))
            self._jitted_fns[idx] = fn
        return fn

    def _run(self, idx: Tuple[int, ...], leaves) -> jnp.ndarray:
        STATS.launches += 1
        # persist the packing buffer only for subsets the hot path has
        # registered via take/put (the canary's rotating slices): the
        # off-hot-path full-state digests (snapshot certification, canary
        # init/refresh) would otherwise pin ~1x packed-state HBM for the
        # plan's lifetime — eating the very saving donation buys
        persist = idx in self._pack_bufs
        buf, table = self._jitted_digest(idx)(self.take_buffer(idx), leaves)
        if persist:
            self.put_buffer(idx, buf)
        else:
            del self._pack_bufs[idx]
        return table

    # -- public digesting --------------------------------------------------

    def digest_table(self, tree) -> jnp.ndarray:
        """(n_leaves, 2) int32 digest table, on device.  ONE fused launch
        (in-place pack + row digest), zero host syncs — the replacement
        for per-leaf ``checksum``.  The packing buffer persists (and the
        call is allocation-free) only for hot-path-registered subsets;
        see ``take_buffer``."""
        idx = tuple(range(self.n_leaves))
        return self._run(idx, self.leaves(tree))

    def digest_subset(self, tree, indices: Sequence[int]) -> jnp.ndarray:
        """(len(indices), 2) digest table for the selected leaves — one
        launch covering only those leaves' tiles (the rotating-canary read
        slice)."""
        idx = tuple(indices)
        if not idx:
            return jnp.zeros((0, 2), jnp.int32)
        leaves = self.leaves(tree)
        return self._run(idx, [leaves[i] for i in idx])

    def digest_dict(self, tree) -> Dict[str, np.ndarray]:
        """Host-side per-leaf digests: one launch + ONE transfer (the seed
        paid one launch and one transfer per leaf)."""
        table = fetch(self.digest_table(tree))
        return {k: table[i] for i, k in enumerate(self.keys)}

    def verify(self, tree, reference: Dict[str, np.ndarray]) -> List[str]:
        """Leaf paths whose digest no longer matches ``reference`` — one
        launch + one transfer; used by snapshot/rung verification."""
        current = self.digest_dict(tree)
        bad = []
        for k, ref_digest in reference.items():
            cur = current.get(k)
            if cur is None or not np.array_equal(cur, ref_digest):
                bad.append(k)
        return sorted(bad)


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict[object, DigestPlan] = {}


def _signature(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    sig = tuple(sorted(
        (leaf_key(p), jnp.shape(x), jnp.result_type(x).name)
        for p, x in flat))
    return treedef, sig


def plan_for(tree) -> DigestPlan:
    """The cached DigestPlan for ``tree``'s structure.  Keyed by treedef +
    per-leaf (path, shape, dtype), so every state with the same structure —
    every step of a training run — shares one plan and its compiled
    digest functions (no per-step retracing)."""
    treedef, sig = _signature(tree)
    key = (treedef, sig)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        keys = tuple(k for k, _, _ in sig)
        # to_i32 maps every supported dtype to exactly one int32 per
        # element, so the packed size is just the element count.
        sizes = tuple(int(np.prod(shape, dtype=np.int64))
                      for _, shape, _ in sig)
        plan = DigestPlan(treedef, keys, sizes)
        _PLAN_CACHE[key] = plan
    return plan


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _SHARDED_PLAN_CACHE.clear()


# ---------------------------------------------------------------------------
# mesh-sharded digesting (DESIGN.md §5) — shard-local digests under shard_map
#
# On an N-device mesh the detection economics must not change: one combined
# launch and ONE scalar host sync per step, with every device streaming only
# its own addressable shard.  The unit of detection becomes the (leaf, shard)
# pair: each device packs and checksums the tiles of its local block, the
# reference tables grow a leading shard dimension (n_shards, L, 2) and live
# SHARDED over the mesh (each device compares only its own rows, on device),
# and the only cross-device traffic on the no-fault path is the all-reduced
# any(fault) scalar (one pmax over the mesh axes).  Fault-path attribution
# resolves to (leaf, shard), which is what lets recovery restore only the
# injured shard's addressable state (core/recover.py shard_patch rung).
# ---------------------------------------------------------------------------

def mesh_device_order(mesh: Mesh) -> Tuple:
    """Canonical shard order: mesh devices flattened row-major over the
    mesh axes IN ORDER.  Shard id ``d`` everywhere in this subsystem (bad
    masks, reference-table rows, snapshot shard digests, FaultReport
    shards) means the device at this flat position."""
    return tuple(mesh.devices.flatten())


def _leaf_pspec(x) -> P:
    """The leaf's PartitionSpec padded to its rank (shard_map in_specs
    want explicit entries)."""
    spec = tuple(x.sharding.spec)
    return P(*(spec + (None,) * (jnp.ndim(x) - len(spec))))


class ShardedDigestPlan(DigestPlan):
    """Per-shard packing layout + shard_map'd digest functions for one
    (state structure, leaf shardings, mesh) triple.

    The inherited layout (``specs``/tile ranges) is computed over the
    LOCAL shard sizes — every device owns an identical private layout
    because GSPMD shard shapes are uniform — so the whole single-device
    digest core (in-place pack + one ``tile_checksums`` pallas_call
    + exact tile-range combine) runs unchanged INSIDE ``shard_map``, once
    per device, in the same single logical launch.  Global artifacts grow
    a leading shard dim, sharded over all mesh axes flattened:

      * packing buffer   (n_shards, local_padded)  — persistent + donated,
      * digest tables    (n_shards, n_leaves, 2)   — row [d, i] = Fletcher
        digest of leaf i's shard-d local block, bit-identical to
        ``host_checksum`` of that block's bytes (the single-device oracle).

    ``bytes_per_pass`` stays the GLOBAL accounting (sum over shards) so
    the §4.2/§5 cost model reads the same: ~2B/K streamed per step total,
    ~2B/(K·n_shards) per device.
    """

    def __init__(self, mesh: Mesh, treedef, keys: Tuple[str, ...],
                 local_sizes: Tuple[int, ...], pspecs: Tuple[P, ...],
                 local_shapes: Tuple[Tuple[int, ...], ...]):
        super().__init__(treedef, keys, local_sizes)
        self.mesh = mesh
        self.axis_names = tuple(mesh.axis_names)
        self.n_shards = int(mesh.size)
        self.pspecs = pspecs                    # per leaf, canonical order
        self.local_shapes = local_shapes        # per leaf, canonical order
        #: specs for the shard-stacked artifacts: dim 0 distributes over
        #: every mesh axis in order == ``mesh_device_order``
        self.buf_spec = P(self.axis_names, None)
        self.table_spec = P(self.axis_names, None, None)
        # global accounting: every device streams its local pass
        self.local_bytes_per_pass = self.bytes_per_pass
        self.bytes_per_pass = self.bytes_per_pass * self.n_shards
        self._local_digest_fns: Dict[Tuple[int, ...], object] = {}

    # -- local core --------------------------------------------------------

    def local_digest_fn(self, idx: Tuple[int, ...]):
        """The UNWRAPPED per-device digest core ``(local_buf, local_leaves)
        -> (local_buf, (len(idx), 2))`` over the local layout — the piece
        ``check_arm_subcomputation`` embeds inside one shard_map together
        with the on-device compare/arm and the fault-flag all-reduce."""
        fn = self._local_digest_fns.get(idx)
        if fn is None:
            fn = DigestPlan._build_digest_fn(self, idx)
            self._local_digest_fns[idx] = fn
        return fn

    def _local_block(self, i: int, leaf):
        """Reshape a shard_map-local leaf block to the leaf's local shape
        (shard_map hands blocks with size-1 sharded dims, not squeezed)."""
        return leaf.reshape(self.local_shapes[i])

    # -- shard_map wrapper -------------------------------------------------

    def _build_digest_fn(self, idx: Tuple[int, ...]):
        local = self.local_digest_fn(idx)

        def local_fn(buf, *leaves):
            blocks = [self._local_block(i, leaf)
                      for i, leaf in zip(idx, leaves)]
            b, t = local(buf[0], blocks)
            return b[None], t[None]

        fn = jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(self.buf_spec,) + tuple(self.pspecs[i] for i in idx),
            out_specs=(self.buf_spec, self.table_spec),
            check_vma=False)

        def digest(buf, leaves):
            return fn(buf, *leaves)

        return digest

    # -- persistent packing buffers (sharded) ------------------------------

    def take_buffer(self, indices: Optional[Sequence[int]] = None
                    ) -> jnp.ndarray:
        idx = tuple(range(self.n_leaves)) if indices is None \
            else tuple(indices)
        buf = self._pack_bufs.get(idx)
        if buf is None or buf.is_deleted():
            n_rows = sum(self.specs[i].n_rows for i in idx)
            padded = -(-n_rows // TILE_ROWS) * TILE_ROWS * LANES
            buf = jax.device_put(
                jnp.zeros((self.n_shards, padded), jnp.int32),
                NamedSharding(self.mesh, self.buf_spec))
            self._pack_bufs[idx] = buf
        return buf

    def buffer_pointer(self, indices: Optional[Sequence[int]] = None):
        """Per-shard device addresses (tuple, mesh-flat order) — a sharded
        array has one buffer per device, all of which must be stable."""
        idx = tuple(range(self.n_leaves)) if indices is None \
            else tuple(indices)
        buf = self._pack_bufs.get(idx)
        if buf is None:
            return None
        by_dev = {sh.device: sh.data.unsafe_buffer_pointer()
                  for sh in buf.addressable_shards}
        return tuple(by_dev[d] for d in mesh_device_order(self.mesh))

    # -- public digesting --------------------------------------------------
    # digest_table / digest_subset are inherited and now return sharded
    # (n_shards, n, 2) tables; the per-leaf host views index the shard dim.

    def digest_dict(self, tree) -> Dict[str, np.ndarray]:
        """Host per-leaf PER-SHARD digests keyed by path: each value is
        (n_shards, 2).  One launch + one transfer, as unsharded."""
        table = fetch(self.digest_table(tree))        # (D, L, 2)
        return {k: table[:, i] for i, k in enumerate(self.keys)}

    def verify(self, tree, reference: Dict[str, np.ndarray]) -> List[str]:
        """Leaf paths with ANY shard digest mismatching ``reference``
        (values (n_shards, 2), as produced by ``digest_dict``)."""
        current = self.digest_dict(tree)
        bad = []
        for k, ref_digest in reference.items():
            cur = current.get(k)
            if cur is None or not np.array_equal(cur, ref_digest):
                bad.append(k)
        return sorted(bad)


_SHARDED_PLAN_CACHE: Dict[object, ShardedDigestPlan] = {}


def _mesh_key(mesh: Mesh):
    return (tuple(mesh.axis_names), tuple(mesh.shape.values()),
            tuple(d.id for d in mesh.devices.flatten()))


def key_on_mesh(cache_key, mesh_key) -> bool:
    """True when any element of a cache key (plan, NamedSharding, ...)
    carries a ``.mesh`` matching ``mesh_key`` — the shared predicate of
    every module's ``evict_mesh`` (elastic hard loss: executables and
    plans pinned to a dead mesh must be dropped, both to release their
    buffers and so a later drill in the same process cannot hit a
    stale-device executable)."""
    elems = cache_key if isinstance(cache_key, tuple) else (cache_key,)
    for el in elems:
        m = getattr(el, "mesh", None)
        if isinstance(m, Mesh) and _mesh_key(m) == mesh_key:
            return True
    return False


def evict_mesh_plans(mesh) -> int:
    """Drop cached ShardedDigestPlans keyed on ``mesh``."""
    mk = _mesh_key(mesh)
    stale = [k for k in _SHARDED_PLAN_CACHE if k[0] == mk]
    for k in stale:
        del _SHARDED_PLAN_CACHE[k]
    return len(stale)


def sharded_plan_for(tree, mesh: Mesh) -> ShardedDigestPlan:
    """The cached ShardedDigestPlan for ``tree``'s structure on ``mesh``.

    Every leaf must already carry a ``NamedSharding`` on ``mesh`` (i.e. the
    state has been ``device_put`` with its partition specs — see
    ``launch/specs.state_shardings``): the plan's per-shard layout is
    derived from those specs and cached by (mesh, structure, specs), so a
    training run digests through one compiled shard_map program per leaf
    subset with no per-step retracing."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    entries = []
    for path, x in flat:
        sharding = getattr(x, "sharding", None)
        if not isinstance(sharding, NamedSharding):
            raise ValueError(
                f"sharded_plan_for requires NamedSharding leaves; "
                f"{leaf_key(path)} has {type(sharding).__name__} — "
                f"device_put the state with its specs first")
        if _mesh_key(sharding.mesh) != _mesh_key(mesh):
            raise ValueError(
                f"leaf {leaf_key(path)} is sharded on a different mesh")
        local_shape = sharding.shard_shape(jnp.shape(x))
        entries.append((leaf_key(path), _leaf_pspec(x), local_shape,
                        jnp.result_type(x).name))
    entries.sort(key=lambda e: e[0])
    key = (_mesh_key(mesh), treedef,
           tuple((k, tuple(sp), ls, dt) for k, sp, ls, dt in entries))
    plan = _SHARDED_PLAN_CACHE.get(key)
    if plan is None:
        keys = tuple(k for k, _, _, _ in entries)
        local_sizes = tuple(int(np.prod(ls, dtype=np.int64))
                            for _, _, ls, _ in entries)
        pspecs = tuple(sp for _, sp, _, _ in entries)
        local_shapes = tuple(ls for _, _, ls, _ in entries)
        plan = ShardedDigestPlan(mesh, treedef, keys, local_sizes, pspecs,
                                 local_shapes)
        _SHARDED_PLAN_CACHE[key] = plan
    return plan


# ---------------------------------------------------------------------------
# check+arm as a traceable subcomputation — the shared core of every fused
# canary mode (DESIGN.md §4.2).  Building it resolves all host-side plan
# state (digest-fn lookup, tile ranges, arm selection) ONCE; the
# returned function is pure and jit-embeddable, so the same subcomputation
# serves both the standalone fused launches (core/detect.py) and the
# in-step fused mode that runs it inside the jitted, donated training step
# (core/fused_step.py).
# ---------------------------------------------------------------------------

def _arming(arm: Sequence[int], n_leaves: int):
    """``(table, armed) -> table`` with rows ``arm`` replaced by the rows of
    ``armed`` in order: a static select, not a scatter."""
    slot = np.zeros(n_leaves, np.int32)
    slot[list(arm)] = np.arange(len(arm), dtype=np.int32)
    hit = np.zeros((n_leaves, 1), bool)
    hit[list(arm)] = True

    def arming(table, armed):
        return jnp.where(hit, armed.astype(table.dtype)[slot], table)

    return arming


def check_arm_subcomputation(plan: DigestPlan, chk: Sequence[int],
                             arm: Sequence[int]):
    """Build the fused check+arm digest core for one canary rotation.

    Returns ``(fn, union)`` where ``union = tuple(chk) + tuple(arm)`` names
    the packing-buffer subset (``plan.take_buffer(union)``) and

        fn(buf, leaves, ref_read, ref_write)
            -> (buf, any_mismatch, bad_mask, new_write)

    digests ``leaves`` (the chk-slice leaves followed by the arm-slice
    leaves, possibly drawn from two state versions) in ONE pallas launch,
    compares the first ``len(chk)`` digests against rows ``chk`` of
    ``ref_read`` on device, and arms the remaining digests into rows
    ``arm`` of ``ref_write`` (in place when the caller donates it).
    Pure/traceable: no host-side plan lookups survive into the traced
    path, so callers may embed ``fn`` inside their own jit — including a
    jitted step function that donates its state (core/fused_step.py).

    For a ``ShardedDigestPlan`` the same signature is served by a
    shard_map'd core (one logical launch; every device digests and
    compares only its own shard rows): ``ref_read``/``ref_write`` are the
    sharded (n_shards, L, 2) generation tables, ``bad_mask`` is the
    sharded (n_shards, len(chk)) per-(leaf, shard) mismatch matrix that
    stays on device until fault-path attribution, and ``any_mismatch`` is
    the all-reduced (pmax over every mesh axis) replicated scalar — the
    ONLY cross-device communication on the no-fault path.
    """
    if isinstance(plan, ShardedDigestPlan):
        return _sharded_check_arm_subcomputation(plan, chk, arm)
    chk = tuple(chk)
    arm = tuple(arm)
    union = chk + arm
    digest = plan.digest_fn(union)
    chk_rows = np.asarray(chk, np.int32)
    nc = len(chk)
    arming = _arming(arm, plan.n_leaves)

    def fn(buf, leaves, ref_read, ref_write):
        buf, table = digest(buf, leaves)    # ONE fused launch
        bad = jnp.any(table[:nc] != ref_read[chk_rows], axis=1) \
            if nc else jnp.zeros((0,), bool)
        new_write = arming(ref_write, table[nc:]) if arm else ref_write
        return buf, jnp.any(bad), bad, new_write

    return fn, union


def _sharded_check_arm_subcomputation(plan: ShardedDigestPlan,
                                      chk: Sequence[int],
                                      arm: Sequence[int]):
    """Mesh variant of ``check_arm_subcomputation`` — one shard_map whose
    body runs the per-device digest core, the on-device compare of the
    device's own reference rows, the in-place arm of the device's own
    write rows, and the any(fault) all-reduce."""
    chk = tuple(chk)
    arm = tuple(arm)
    union = chk + arm
    local_digest = plan.local_digest_fn(union)
    chk_rows = np.asarray(chk, np.int32)
    nc = len(chk)
    arming = _arming(arm, plan.n_leaves)
    axes = plan.axis_names

    def local_fn(buf, ref_read, ref_write, *leaves):
        blocks = [plan._local_block(i, leaf)
                  for i, leaf in zip(union, leaves)]
        b, table = local_digest(buf[0], blocks)       # per-device local pass
        bad = jnp.any(table[:nc] != ref_read[0, chk_rows], axis=1) \
            if nc else jnp.zeros((0,), bool)
        # the fault flag is the only cross-device hop on the no-fault path
        flag = jax.lax.pmax(jnp.any(bad).astype(jnp.int32), axes) > 0
        new_write = arming(ref_write[0], table[nc:])[None] \
            if arm else ref_write
        return b[None], flag, bad[None], new_write

    smapped = jax.shard_map(
        local_fn, mesh=plan.mesh,
        in_specs=(plan.buf_spec, plan.table_spec, plan.table_spec)
        + tuple(plan.pspecs[i] for i in union),
        out_specs=(plan.buf_spec, P(), P(plan.axis_names, None),
                   plan.table_spec),
        check_vma=False)

    def fn(buf, leaves, ref_read, ref_write):
        return smapped(buf, ref_read, ref_write, *leaves)

    return fn, union


# ---------------------------------------------------------------------------
# host digest path — the oracle of the device digests, and the certificate
# of mesh micro-snapshots' host copies (DESIGN.md §4.2).  Bit-identical to
# the kernel: numpy uint32 arithmetic wraps mod 2^32 exactly like the int32
# device math.
# ---------------------------------------------------------------------------

def _host_i32(x: np.ndarray) -> np.ndarray:
    """Host mirror of ``ref.to_i32``: flat int32 view of the raw bits."""
    a = np.ascontiguousarray(x)
    if a.dtype.itemsize == 4:          # float32 / int32 / uint32: bit view
        return a.reshape(-1).view(np.int32)
    if a.dtype.itemsize == 2:          # bf16 / f16 / i16 / u16: zero-extend
        return a.reshape(-1).view(np.uint16).astype(np.int32)
    if a.dtype.itemsize == 1:          # i8 / u8: zero-extend
        return a.reshape(-1).view(np.uint8).astype(np.int32)
    if a.dtype == np.int64:            # truncate, as jnp astype does
        return a.reshape(-1).astype(np.int32)
    return np.ascontiguousarray(
        a.astype(np.float32)).reshape(-1).view(np.int32)


def host_checksum(x) -> np.ndarray:
    """Fletcher digest int32[2] of a HOST array — bit-identical to
    ``ops.checksum``/``ref.checksum_ref`` of the same bytes, with zero
    device work (no upload, no launch, no sync)."""
    f = _host_i32(np.asarray(x)).view(np.uint32)
    idx = np.arange(1, f.shape[0] + 1, dtype=np.uint32)
    s1 = np.add.reduce(f, dtype=np.uint32)
    s2 = np.add.reduce(f * idx, dtype=np.uint32)
    return np.array([s1, s2], dtype=np.uint32).view(np.int32)


def host_tree_checksums(tree) -> Dict[str, np.ndarray]:
    """Per-leaf host digests keyed by path — the snapshot-certification
    twin of ``ops.tree_checksums``, computed on the host DMA copy."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {leaf_key(p): host_checksum(leaf) for p, leaf in flat}


def host_verify_tree(tree, reference: Dict[str, np.ndarray]) -> List[str]:
    """Leaf paths of a HOST tree whose digest no longer matches
    ``reference`` — snapshot verification on the fault path, device-free."""
    current = host_tree_checksums(tree)
    bad = []
    for k, ref_digest in reference.items():
        cur = current.get(k)
        if cur is None or not np.array_equal(cur, ref_digest):
            bad.append(k)
    return sorted(bad)


def shard_indices(x) -> List[Tuple]:
    """Per-shard global index tuples of a NamedSharding array, in
    mesh-flat shard order — the slice each shard id addresses.  This is
    the metadata micro-snapshots store so shard-local restore can carve a
    single shard's bytes out of a host copy."""
    m = x.sharding.devices_indices_map(jnp.shape(x))
    return [m[d] for d in mesh_device_order(x.sharding.mesh)]


def host_shard_checksums(x) -> np.ndarray:
    """(n_shards, 2) host digests of a sharded array, shard order matching
    the sharded digest tables — the single-device uint32 oracle the kernel
    path is asserted bit-identical against.  (Mesh snapshot certification
    does NOT route through here: ``core/microcheckpoint.py`` hashes its
    stored host copy's slices directly via ``host_checksum``, so it never
    re-fetches the device.)"""
    host = np.asarray(x)
    return np.stack([host_checksum(host[idx]) for idx in shard_indices(x)])


# ---------------------------------------------------------------------------
# single-flip localisation — triage's certificate engine.  The Fletcher pair
# (s1, s2) over a leaf's packed words is an error-locating code for the
# single-bit-flip channel: one flipped bit b in word j shifts the digests by
#
#     delta1 = s1' - s1 = d            (mod 2^32),   d = +-2^b
#     delta2 = s2' - s2 = (j + 1) * d  (mod 2^32)
#
# so the (bit, word) coordinates of the flip are solvable from the reference
# digest the canary already holds — no second copy of the data needed.
# ---------------------------------------------------------------------------

def _inv_odd_u32(w: int) -> int:
    """Multiplicative inverse of odd ``w`` mod 2^32 (Newton iteration)."""
    inv = w & 0xFFFFFFFF
    for _ in range(5):
        inv = (inv * (2 - w * inv)) & 0xFFFFFFFF
    return inv


def locate_single_flip(ref_pair, cur_pair, n_words: int):
    """Solve the digest pair for a single flipped bit.

    Args: reference and current int32[2] digests of the same leaf (or
    shard slice) and the packed word count.  Returns ``(bit, delta,
    candidates)`` — the flipped bit index, the mod-2^32 word delta
    (``old_word = (cur_word - delta) & 0xFFFFFFFF``), and the candidate
    flat word indices j (several only when ``n_words > 2^(32-bit)``) — or
    ``None`` when the deltas are inconsistent with EVERY single-bit flip
    (multi-word or multi-bit damage: the caller must escalate).
    """
    ref = np.asarray(ref_pair).view(np.uint32).reshape(-1)
    cur = np.asarray(cur_pair).view(np.uint32).reshape(-1)
    d1 = int((int(cur[0]) - int(ref[0])) & 0xFFFFFFFF)
    d2 = int((int(cur[1]) - int(ref[1])) & 0xFFFFFFFF)
    if d1 == 0:
        return None  # a single flip always moves s1 by a non-zero +-2^b
    bit = (d1 & -d1).bit_length() - 1  # trailing zeros of d1
    w = d1 >> bit
    # d = +2^b gives w = 1; d = -2^b mod 2^32 gives w = 2^(32-b) - 1
    if w not in (1, (1 << (32 - bit)) - 1):
        return None
    q = (d2 * _inv_odd_u32(w)) & 0xFFFFFFFF
    if q & ((1 << bit) - 1):
        return None  # (j+1)*2^b must have b low zero bits
    m = q >> bit  # j + 1 mod 2^(32-bit)
    period = 1 << (32 - bit)
    first = m if m != 0 else period
    candidates = [j1 - 1 for j1 in range(first, n_words + 1, period)]
    if not candidates:
        return None
    return bit, d1, candidates
