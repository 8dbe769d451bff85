"""Paged KV pool for the serving engine (DESIGN.md §6).

The dense engine stacks one ``[max_len]``-capacity decode cache per slot,
so every admitted request pays worst-case HBM no matter how short it is.
Here every cache leaf becomes a shared **block pool** —
``(n_blocks, block_size, count, KV, D)`` — plus a per-slot **block
table** ``(S, max_blocks)``: a request owns exactly
``ceil((len(prompt) + 1 + max_new_tokens) / block_size)`` blocks, and
admission is a block-budget decision (`BlockAllocator`).

Layout invariants the engine's resilience contract leans on:

* **Block 0 is scratch.**  Unallocated block-table entries and all masked
  scatter lanes point at it, so every data-movement op has a fixed shape
  regardless of how many blocks a slot really owns (0 retraces across
  alloc/free churn).  Its bytes are junk by design; nothing reads them —
  attention masks unwritten positions via ``cache_kpos`` — but the canary
  still digests it, so every out-of-step write that can touch it (any
  admission scatter) must be followed by a block-0 digest refresh.
* **Blocks are zeroed on allocation** (`zero_blocks`): a freed block may
  hold non-finite bytes from an evicted/poisoned sequence, and a masked
  attention weight times Inf/NaN is NaN — zeroing keeps masked garbage
  exactly 0-weighted (the bit-exactness chain in DESIGN.md §6).
* **The hot-path gather is a pure copy** (`kernels/paged_kv.py`): the
  vmapped decode step runs *unmodified* on the gathered per-slot view,
  which is what makes paged-vs-dense bit-exactness hold by construction.

The canary view (`paged_canary_view`) digests the pool at (leaf, block)
granularity plus a per-slot ``pos`` unit; `block → owning slot` is a host
lookup in the allocator, so a fault injures *blocks* and only transitively
the slot that owns them — a flip on a free block evicts nobody.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.detect import block_view, slot_view
from repro.kernels.paged_kv import gather_blocks

tree_map = jax.tree_util.tree_map


class AdmissionError(ValueError):
    """Request can never be admitted: its worst-case KV footprint
    (``len(prompt) + 1 + max_new_tokens`` positions) exceeds the engine's
    per-slot budget (dense: ``max_len``; paged: ``max_blocks`` blocks) or
    the whole pool.  Permanent — retrying cannot help."""


class PoolSaturated(RuntimeError):
    """Transient block shortage: the request fits the per-slot budget but
    the pool's free list is currently too short.  Retry after a running
    request completes and returns its blocks."""


def blocks_needed(prompt_len: int, max_new_tokens: int,
                  block_size: int) -> int:
    """Worst-case block count for a request: every prompt position, every
    generated token, and the one-past-the-end write slot."""
    need = prompt_len + 1 + max_new_tokens
    return -(-need // block_size)


class BlockAllocator:
    """Host-side free-list allocator over the shared pool.

    Block 0 is reserved as scratch and never handed out.  Allocation and
    free order are deterministic (LIFO free list) so seeded runs admit
    identical block tables — the serving reproducibility tests depend on
    it.  ``owner`` maps physical block id → owning slot; the canary's
    fault path uses it to translate (leaf, block) attribution into the
    slot to evict (or into "free block, nobody to evict")."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (block 0 is scratch)")
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))
        self._owned: Dict[int, List[int]] = {}
        self.owner: Dict[int, int] = {}

    @property
    def capacity(self) -> int:
        return self.n_blocks - 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    def allocate(self, slot: int, n: int) -> List[int]:
        if slot in self._owned:
            raise ValueError(f"slot {slot} already owns blocks")
        if n > len(self._free):
            raise PoolSaturated(
                f"need {n} blocks, {len(self._free)} free "
                f"(pool capacity {self.capacity})")
        blocks = [self._free.pop() for _ in range(n)]
        self._owned[slot] = blocks
        for b in blocks:
            self.owner[b] = slot
        return blocks

    def free(self, slot: int) -> List[int]:
        blocks = self._owned.pop(slot, [])
        for b in blocks:
            del self.owner[b]
        self._free.extend(reversed(blocks))
        return blocks

    def owned(self, slot: int) -> List[int]:
        return list(self._owned.get(slot, ()))


# ---------------------------------------------------------------------------
# Pool construction and data movement
#
# Shape conventions (B=1 per slot throughout):
#   per-slot cache leaf (dense layout) : (count, 1, cap, KV, D)
#   pool leaf                          : (n_blocks, block_size, count, KV, D)
#   gathered per-slot view             : (S, count, 1, cap, KV, D)
# with cap = max_blocks * block_size == max_len (rounded up by the engine).
# ---------------------------------------------------------------------------

def paged_supported(model, model_cfg, per_slot, max_len: int) -> bool:
    """Can this family's decode cache be paged?  Requires the chunk-prefill
    entry point, linear (non-ring) per-position caches of exactly
    ``max_len`` capacity, and 1-D rope (no m-rope / patch inputs)."""
    if getattr(model, "prefill_chunk", None) is None:
        return False
    if getattr(model_cfg, "m_rope", False) or getattr(model_cfg, "patch_dim", 0):
        return False
    if not (isinstance(per_slot, dict) and set(per_slot) == {"groups", "pos"}):
        return False
    leaves = jax.tree_util.tree_leaves(per_slot["groups"])
    return bool(leaves) and all(
        l.ndim == 5 and l.shape[1] == 1 and l.shape[2] == max_len
        for l in leaves)


def make_block_pool(per_slot, n_blocks: int, block_size: int):
    """Block-major pool from a per-slot dense cache template (B=1)."""
    def pool_leaf(l):
        count = l.shape[0]
        feat = l.shape[3:]
        return jnp.zeros((n_blocks, block_size, count) + feat, l.dtype)
    return {"groups": tree_map(pool_leaf, per_slot["groups"])}


def gathered_cache(pool, bt, pos):
    """Materialise the dense slot-major cache view the vmapped decode step
    expects, via the Pallas block gather (one DMA program per
    (slot, logical block)).

    Rows at positions >= ``pos[s]`` are zeroed: block-table padding points
    at scratch block 0, whose bytes can be non-finite (inactive lanes
    scatter junk there), and a masked attention weight of exactly 0.0
    times NaN is NaN.  The dense cache keeps those rows as exact zeros
    (prefill zero-padding), so zeroing here is what makes the gathered
    view bit-identical to the dense one."""
    def g(leaf):
        out = gather_blocks(leaf, bt)
        S, mb, bs, count = out.shape[:4]
        feat = out.shape[4:]
        out = out.reshape((S, mb * bs, count) + feat)
        valid = jnp.arange(mb * bs, dtype=jnp.int32)[None, :] < pos[:, None]
        out = jnp.where(
            valid.reshape((S, mb * bs) + (1,) * (len(feat) + 1)),
            out, jnp.zeros((), out.dtype))
        out = jnp.moveaxis(out, 1, 2)       # (S, count, cap, *feat)
        return out[:, :, None]              # (S, count, 1, cap, *feat)
    return {"groups": tree_map(g, pool["groups"]), "pos": pos}


def scatter_token(pool, ngroups, bt, pos, amask, block_size: int):
    """Write each active lane's newly decoded cache row back to the pool.

    ngroups: the post-decode gathered view's groups (leaves
    (S, count, 1, cap, *feat)) — the row at position ``pos[s]`` is the
    only one the decode step changed.  Inactive lanes redirect to scratch
    block 0 (fixed-shape scatter; no retrace as lanes come and go)."""
    bs = block_size
    mb = bt.shape[1]
    S = pos.shape[0]
    p = jnp.clip(pos, 0, mb * bs - 1)
    bl = jnp.clip(p // bs, 0, mb - 1)
    bids = jnp.where(amask, jnp.take_along_axis(bt, bl[:, None], axis=1)[:, 0],
                     0)
    offs = jnp.where(amask, p % bs, 0)

    def upd(pool_leaf, nl):
        x = nl[:, :, 0]                     # (S, count, cap, *feat)
        idx = p.reshape((S,) + (1,) * (x.ndim - 1))
        vals = jnp.take_along_axis(x, idx, axis=2)[:, :, 0]
        return pool_leaf.at[bids, offs].set(vals.astype(pool_leaf.dtype))

    return {"groups": tree_map(upd, pool["groups"], ngroups)}


def scatter_span(pool, new_kv_groups, bt_row, start, valid, block_size: int):
    """Scatter a prefilled span (positions ``start .. start+valid-1``) of
    one slot into the pool.  new_kv_groups leaves: (count, 1, C, *feat).
    Rows past ``valid`` redirect to scratch block 0."""
    bs = block_size
    mb = bt_row.shape[0]

    def upd(pool_leaf, nl):
        C = nl.shape[2]
        j = start + jnp.arange(C, dtype=jnp.int32)
        ok = jnp.arange(C, dtype=jnp.int32) < valid
        bl = jnp.clip(j // bs, 0, mb - 1)
        bids = jnp.where(ok, bt_row[bl], 0)
        offs = jnp.where(ok, j % bs, 0)
        x = jnp.moveaxis(nl[:, 0], 1, 0)    # (C, count, *feat)
        return pool_leaf.at[bids, offs].set(x.astype(pool_leaf.dtype))

    return {"groups": tree_map(upd, pool["groups"], new_kv_groups)}


def zero_blocks(pool, bids):
    """Zero the pool rows of the given physical blocks (padded index
    vectors repeat block 0 — harmless, it's scratch)."""
    def z(leaf):
        zeros = jnp.zeros((bids.shape[0],) + leaf.shape[1:], leaf.dtype)
        return leaf.at[bids].set(zeros)
    return {"groups": tree_map(z, pool["groups"])}


def ctx_from_pool(pool, bt_row, block_size: int, pos0=None):
    """One slot's context in dense cache layout (admission path — plain
    jnp gather, not the hot-path kernel).  Returns groups with leaves
    (count, 1, cap, *feat).  ``pos0`` (traced int32) zeroes rows at
    positions >= pos0 — same non-finite-scratch guard as
    ``gathered_cache``."""
    def g(leaf):
        t = jnp.take(leaf, bt_row, axis=0)  # (mb, bs, count, *feat)
        mb, bs, count = t.shape[:3]
        t = t.reshape((mb * bs, count) + t.shape[3:])
        if pos0 is not None:
            valid = jnp.arange(mb * bs, dtype=jnp.int32) < pos0
            t = jnp.where(valid.reshape((mb * bs,) + (1,) * (t.ndim - 1)),
                          t, jnp.zeros((), t.dtype))
        t = jnp.moveaxis(t, 0, 1)           # (count, cap, *feat)
        return t[:, None]                   # (count, 1, cap, *feat)
    return {"groups": tree_map(g, pool["groups"])}


def ctx_kpos(pos0, cap: int):
    """Absolute key positions of a linear context of ``cap`` rows of which
    the first ``pos0`` are written (<0 = unwritten, masked)."""
    j = jnp.arange(cap, dtype=jnp.int32)
    return jnp.where(j < pos0, j, -1)[None, :]


def paged_canary_view(pool, pos, n_blocks: int, n_slots: int):
    """Digest view: (leaf, block) units over the pool + a per-slot ``pos``
    unit.  Block tables / activity masks / last-token buffers stay
    uncovered control plane (host-rebuildable, like the dense engine's
    token buffer)."""
    view = block_view(pool, n_blocks)
    view.update(slot_view({"pos": pos}, n_slots))
    return view
