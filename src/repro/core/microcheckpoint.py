"""Micro-checkpoints — the paper's Algorithm 2 at training-loop scale.

The paper spills induction-variable *initial values* to the stack so Eq. (1)
is evaluable at recovery time.  Our two-tier analogue:

* **IV micro-checkpoint** (every step, bytes): the iv block + its digests.
  This is literally the paper's mechanism — the loop-control initial/current
  values, kept where the recovery runtime can always reach them.
* **state snapshot** (every K steps, double-buffered, in-HBM/host-RAM):
  a full train-state copy + per-leaf digests, giving the replay rung a
  nearby anchor.  No disk I/O on the recovery path — that is the entire
  near-zero-downtime claim vs classic C/R.

Off a mesh the snapshot's certificate is computed on the device: the
per-leaf Fletcher digests of the live state come from the canary's own
``DigestPlan``, in leaf groups of at most ``DIGEST_GROUP_BYTES`` packed
bytes, and the restore re-digests the UPLOADED tree against them, so what
is replayed is exactly what was digested.  Mesh snapshots keep their
per-(leaf, shard) host digests (``shard_digests``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import digest as kdigest

#: cap on the packed int32 bytes one device digest program covers.  A
#: whole-state digest needs a packing buffer the size of the packed state
#: and a row-digest output padded to as much again; grouping leaves bounds
#: that transient HBM and keeps the number of group programs small (a
#: 1.8 GB bf16/f32 training state packs to ~2.2 GB: twelve groups).
#: A leaf larger than the cap forms a group alone.
DIGEST_GROUP_BYTES = 256 << 20


def host_copy(tree):
    """Materialised host copy of a device tree, safe under donation.

    Routed through a device-side temp: converting the LIVE array to
    numpy can cache a zero-copy host view on it (the bf16 path does),
    which pins the buffer and silently vetoes ``donate_argnums``
    in-place reuse for the array's lifetime.  The temp absorbs the
    view/cache and is dropped; the copy owns its bytes either way.
    Shared by the micro-checkpointer and ``checkpoint.store`` — every
    host copy of live state must go through here.
    """
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jnp.array(x, copy=True)), tree)


_host_copy = host_copy


def digest_groups(plan) -> Tuple[Tuple[Tuple[int, ...], ...], int]:
    """Contiguous runs of ``plan``'s leaves (canonical order) whose packed
    bytes stay within ``DIGEST_GROUP_BYTES``, and the bytes their packing
    buffers stream.  Concatenating the groups' tables gives the whole
    table in canonical order."""
    groups: List[Tuple[int, ...]] = []
    cur: List[int] = []
    size = 0
    for sp in plan.specs:
        b = sp.n_rows * kdigest.LANES * 4
        if cur and size + b > DIGEST_GROUP_BYTES:
            groups.append(tuple(cur))
            cur, size = [], 0
        cur.append(sp.index)
        size += b
    if cur:
        groups.append(tuple(cur))
    tile = kdigest.TILE_ROWS
    nbytes = sum(-(-sum(plan.specs[i].n_rows for i in g) // tile) * tile
                 for g in groups) * kdigest.LANES * 4
    return tuple(groups), nbytes


@dataclass
class Snapshot:
    step: int
    state: object
    digests: Dict[str, np.ndarray]
    nbytes: int = 0                  # cached at snapshot time
    wall: float = field(default_factory=time.time)
    #: mesh metadata (sharded loops; DESIGN.md §5): per leaf, the global
    #: index tuple each shard id addresses (mesh-flat device order) and
    #: the per-shard host digests of exactly those bytes.  This is what
    #: lets the shard_patch recovery rung carve a SINGLE injured shard's
    #: bytes out of the host copy, certify them, and restore only that
    #: shard's addressable state.
    shard_slices: Optional[Dict[str, List]] = None
    shard_digests: Optional[Dict[str, np.ndarray]] = None


class MicroCheckpointer:
    """Double-buffered in-memory snapshots + per-step IV micro-checkpoints.

    ``ctx`` (a ``DistContext`` with a live mesh) switches snapshots to
    shard-aware mode: alongside the per-leaf digests, every snapshot
    records each leaf's shard→index map and per-shard host digests
    (``Snapshot.shard_slices``/``shard_digests``), so recovery can verify
    and restore individual (leaf, shard) units instead of whole states.
    The host copy itself is unchanged (one DMA read of the live state);
    on a mesh every digest is a host-side hashing pass over the copy's
    bytes, off the hot path.  Without a mesh the per-leaf digests are
    device digests of the live state (``_device_digests``)."""

    def __init__(self, interval: int = 8, keep: int = 2, ctx=None):
        self.interval = max(1, interval)
        self.keep = max(1, keep)
        self.ctx = ctx if (ctx is not None and ctx.enabled) else None
        self.snapshots: List[Snapshot] = []
        self.iv_log: Dict[int, Dict[str, int]] = {}
        #: ``digest_groups`` of each DigestPlan this checkpointer digested
        self._groups: Dict[kdigest.DigestPlan, Tuple] = {}

    # -- per-step (bytes) ----------------------------------------------------

    def record_iv(self, step: int, iv: Dict) -> None:
        self.iv_log[step] = {k: int(v) for k, v in iv.items()}
        # bounded memory: keep a window
        if len(self.iv_log) > 4 * self.interval:
            for s in sorted(self.iv_log)[:-2 * self.interval]:
                del self.iv_log[s]

    # -- every K steps (double-buffered) --------------------------------------

    def maybe_snapshot(self, step: int, state) -> bool:
        if step % self.interval != 0:
            return False
        self.snapshot(step, state)
        return True

    def snapshot(self, step: int, state) -> None:
        # Off a mesh the digests are device digests of the live state,
        # taken before the host copy: both read the buffer version the
        # next (donating) step consumes.  On a mesh
        # they are host digests of the copy, per leaf and per shard.
        # Spans: ``snapshot`` over ``snapshot.digest`` (with the packed
        # ``bytes`` it digested and the ``groups`` it launched, off a mesh)
        # and ``snapshot.copy`` (the host copy, with its bytes).
        shard_slices = shard_digests = None
        with obs.span("snapshot", step=step):
            if self.ctx is None:
                with obs.span("snapshot.digest") as timed:
                    digests, timed.attrs["bytes"], timed.attrs["groups"] = \
                        self._device_digests(state)
            with obs.span("snapshot.copy") as copy:
                host = _host_copy(state)
                nbytes = sum(leaf.nbytes for leaf in
                             jax.tree_util.tree_leaves(host))
                copy.attrs["bytes"] = nbytes
            if self.ctx is not None:
                with obs.span("snapshot.digest"):
                    digests, shard_slices, shard_digests = \
                        self._host_digests(state, host)
        snap = Snapshot(step=step, state=host, digests=digests,
                        nbytes=nbytes, shard_slices=shard_slices,
                        shard_digests=shard_digests)
        self.snapshots.append(snap)
        if len(self.snapshots) > self.keep:
            self.snapshots.pop(0)

    def _device_digests(self, tree) -> Tuple[Dict[str, np.ndarray], int,
                                             int]:
        """Per-leaf Fletcher digests of a device tree, keyed by path, with
        the packed bytes digested and the programs launched: one
        ``DigestPlan.digest_subset`` program per leaf group
        (``digest_groups``, cached per plan), run one after another, and
        ONE host fetch of the concatenated table."""
        plan = kdigest.plan_for(tree)
        cached = self._groups.get(plan)
        if cached is None:
            cached = self._groups[plan] = digest_groups(plan)
        groups, nbytes = cached
        # one group in flight: a program's packing buffer is allocated
        # when it is enqueued, so enqueueing every group ahead would hold
        # the whole packed state at once
        table = kdigest.fetch(jnp.concatenate(
            [plan.digest_subset(tree, g).block_until_ready()
             for g in groups]))
        return ({k: table[i] for i, k in enumerate(plan.keys)}, nbytes,
                len(groups))

    def _host_digests(self, state, host):
        """Mesh snapshots: per-leaf host digests of the copy, and each
        leaf's shard index map with per-shard digests."""
        # index maps from the LIVE shardings, digests from the host copy's
        # bytes (never re-read the device) — per (leaf, shard), in
        # mesh-flat shard order
        shard_slices, shard_digests = {}, {}
        flat_live = jax.tree_util.tree_flatten_with_path(state)[0]
        flat_host = jax.tree_util.tree_leaves(host)
        for (path, live), hleaf in zip(flat_live, flat_host):
            key = kdigest.leaf_key(path)
            idxs = kdigest.shard_indices(live)
            shard_slices[key] = idxs
            # hash each DISTINCT slice once: a replicated leaf maps
            # every shard to the same full-leaf index, and hashing it
            # D times would make snapshots O(replicated_bytes x D)
            seen: Dict[Tuple, np.ndarray] = {}
            rows = []
            for idx in idxs:
                k = tuple((s.start, s.stop, s.step)
                          if isinstance(s, slice) else s for s in idx)
                if k not in seen:
                    seen[k] = kdigest.host_checksum(hleaf[idx])
                rows.append(seen[k])
            shard_digests[key] = np.stack(rows)
        return kdigest.host_tree_checksums(host), shard_slices, shard_digests

    def latest(self, before: Optional[int] = None) -> Optional[Snapshot]:
        cands = [s for s in self.snapshots
                 if before is None or s.step <= before]
        return cands[-1] if cands else None

    def verify(self, snap: Snapshot, state) -> List[str]:
        """Leaf paths whose digest differs from the snapshot's — the replay
        rung's exact-or-abort gate (a rotted snapshot must not silently
        replay).  Off a mesh ``state`` is the snapshot as UPLOADED, and
        its device digests are compared, so a fault in the copy down, in
        host RAM or in the upload is caught; on a mesh the stored host
        bytes are re-hashed.  Span ``snapshot.verify`` (with ``bytes`` and
        ``groups`` off a mesh)."""
        with obs.span("snapshot.verify") as timed:
            if self.ctx is not None:
                return kdigest.host_verify_tree(snap.state, snap.digests)
            current, timed.attrs["bytes"], timed.attrs["groups"] = \
                self._device_digests(state)
        return sorted(k for k, ref in snap.digests.items()
                      if k not in current
                      or not np.array_equal(current[k], ref))

    def verify_shards(self, snap: Snapshot,
                      shards: Dict[str, List[int]]) -> List[str]:
        """Digest-verify only the named (leaf, shard) units of a snapshot
        — the shard_patch rung's exact-or-abort gate.  Hashes ONLY the
        bytes that would be restored; returns ``"leaf@shard"`` names that
        fail (empty = all certified).  Host-side, no device work."""
        if snap.shard_slices is None or snap.shard_digests is None:
            return sorted(f"{k}@{d}" for k, ds in shards.items() for d in ds)
        host = {kdigest.leaf_key(p): leaf for p, leaf in
                jax.tree_util.tree_flatten_with_path(snap.state)[0]}
        bad = []
        for key, ids in shards.items():
            idxs = snap.shard_slices.get(key)
            ref = snap.shard_digests.get(key)
            leaf = host.get(key)
            for d in ids:
                if idxs is None or ref is None or leaf is None \
                        or d >= len(idxs):
                    bad.append(f"{key}@{d}")
                    continue
                cur = kdigest.host_checksum(leaf[idxs[d]])
                if not np.array_equal(cur, ref[d]):
                    bad.append(f"{key}@{d}")
        return sorted(bad)

    @property
    def memory_bytes(self) -> int:
        """Resident snapshot footprint — cached per snapshot at capture
        time (the seed re-materialised every leaf with ``np.asarray`` on
        each property read)."""
        return sum(s.nbytes for s in self.snapshots)
