"""jit'd public wrappers around the Pallas kernels.

Handles: arbitrary shapes/dtypes (bit-cast + pad to tile multiples), exact
digest recombination across tiles, and pytree-level orchestration (leaf
digests for whole train states).  Every kernel decides interpret vs
compiled mode itself (``kernels.backend.interpret_mode``).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import checksum as _ck
from repro.kernels import digest as _dg
from repro.kernels import parity as _pk
from repro.kernels import ref as _ref
from repro.kernels import vote as _vk
from repro.kernels.digest import leaf_key, plan_for  # noqa: F401 (re-export)

TILE = _ck.TILE  # int32 elements per kernel tile


def _tiles(x) -> Tuple[jnp.ndarray, int]:
    """Flat int32 view padded and reshaped to (nt, TILE_ROWS, LANES)."""
    flat = _ref.to_i32(x)
    n = flat.shape[0]
    nt = max(1, -(-n // TILE))
    flat = jnp.pad(flat, (0, nt * TILE - n))
    return flat.reshape(nt, _ck.TILE_ROWS, _ck.LANES), n


@jax.jit
def checksum(x) -> jnp.ndarray:
    """Two-term Fletcher digest int32[2] of the raw bits of ``x``.

    The tile partials of ``tile_checksums`` fold into one digest with the
    fused digest's own ``combine`` over the array's one tile range.
    """
    tiles, _ = _tiles(x)
    nt = tiles.shape[0]
    return _ck.combine(_ck.tile_checksums(tiles), [0], [nt])[0]


@jax.jit
def vote3(a, b, c):
    """Bitwise majority of three equal-shaped arrays, original dtype out."""
    ta, n = _tiles(a)
    tb, _ = _tiles(b)
    tc, _ = _tiles(c)
    out = _vk.vote3_tiles(ta, tb, tc)
    return _ref.from_i32(out.reshape(-1)[:n], a)


@jax.jit
def xor_fold(arrays: Sequence[jnp.ndarray]):
    """Parity of N equal-shaped arrays (original dtype out)."""
    ts = []
    n = None
    for a in arrays:
        t, n = _tiles(a)
        ts.append(t)
    stacked = jnp.stack(ts)  # (R, nt, rows, lanes)
    out = _pk.xor_fold_tiles(stacked)
    return _ref.from_i32(out.reshape(-1)[:n], arrays[0])


@jax.jit
def xor_reconstruct(parity, others: Sequence[jnp.ndarray]):
    """Recover the missing shard from parity + the surviving shards."""
    return xor_fold(list(others) + [parity])


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 0, block_k: int = 0):
    """Model-layout flash attention: q (B, Sq, H, D), k/v (B, Sk, KV, D).

    Handles GQA flattening, block-multiple padding of Sq/Sk and lane-multiple
    (128) padding of D, then calls the Pallas kernel (compiled on TPU,
    interpret elsewhere).  Returns (B, Sq, H, D) in q.dtype.
    """
    from repro.kernels import flash_attention as _fa

    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    bq = block_q or min(_fa.DEFAULT_BLOCK_Q, max(Sq, 16))
    bk = block_k or min(_fa.DEFAULT_BLOCK_K, max(Sk, 16))

    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    # lane alignment: round D up to a multiple of 128 (tiny test dims are
    # left alone — interpret mode has no lane constraint)
    pad_d = (-D) % 128 if D >= 128 else 0

    qt = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, pad_d)))
    kt = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, pad_d)))
    vt = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, pad_d)))

    # (B, S, H, D) -> (B*H, S, D); kv -> (B*KV, S, D).  The kernel's GQA
    # index map assumes q-head-major flattening per batch.
    qf = qt.transpose(0, 2, 1, 3).reshape(B * H, Sq + pad_q, D + pad_d)
    kf = kt.transpose(0, 2, 1, 3).reshape(B * KV, Sk + pad_k, D + pad_d)
    vf = vt.transpose(0, 2, 1, 3).reshape(B * KV, Sk + pad_k, D + pad_d)

    # scale by true D, not padded D: kernel scales by padded; correct it
    o = _fa.flash_attention_bhsd(
        qf * np.sqrt((D + pad_d) / D).astype(qf.dtype),
        kf, vf, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk)
    o = o.reshape(B, H, Sq + pad_q, D + pad_d).transpose(0, 2, 1, 3)
    return o[:, :Sq, :, :D]


# ---------------------------------------------------------------------------
# Pytree-level orchestration — thin wrappers over the fused DigestPlan
# (one pallas launch + one host transfer per call; the seed paid one
# launch and one blocking transfer per LEAF — see DESIGN.md §4.2).
# ---------------------------------------------------------------------------

def tree_checksums(tree) -> Dict[str, np.ndarray]:
    """Digest per leaf, keyed by path string — the Recovery Table's 'key'
    column (the paper keys on (file, line, column) debug tuples; ours is the
    state-leaf path, which plays the same role)."""
    return plan_for(tree).digest_dict(tree)


def subtree_checksums(tree, keys) -> Dict[str, np.ndarray]:
    """Digests for the named leaves only (the rotating-canary read slice —
    the paid 1/K of the detection cost; everything else is modeled as fused
    into the step's write stream).  One launch over the subset's tiles."""
    plan = plan_for(tree)
    kset = set(keys)
    want = [k for k in plan.keys if k in kset]
    idx = [plan.index_of(k) for k in want]
    table = _dg.fetch(plan.digest_subset(tree, idx)) if idx \
        else np.zeros((0, 2), np.int32)
    return {k: table[i] for i, k in enumerate(want)}


def verify_tree(tree, reference: Dict[str, np.ndarray]) -> List[str]:
    """Return leaf paths whose digest no longer matches ``reference``."""
    return plan_for(tree).verify(tree, reference)


def rotating_slice(step: int, n_slices: int, n_leaves: int) -> List[int]:
    """Indices of the leaves checked at ``step`` under the rotating-canary
    schedule (full coverage every n_slices steps at 1/n_slices the cost)."""
    return [i for i in range(n_leaves) if i % n_slices == step % n_slices]
