"""Pallas TPU kernel: tile-granular Fletcher-style checksum.

This is the paper's "zero-overhead detection" made real on TPU: the canary
detector must stream the full train state at HBM bandwidth with no MXU use
and negligible VMEM residency, so it can overlap with step compute.

Layout: the flat int32 view is tiled (TILE_ROWS, LANES) = (256, 128) — one
tile is 128 KiB, and the lane dim matches the VPU's native 128-lane
registers.  The compiled kernel streams ``BLOCK_TILES`` tiles (1 MiB) per
grid step.

``tile_checksums`` digests every TILE of its input in one launch.  Per tile
it writes the two Fletcher sums of each of its 128 lanes, weighted by the
element's offset inside the tile: a lane-dense (2, tiles, 128) output of
1/128 the bytes it reads.  ``combine`` folds tiles into digests over static
contiguous tile ranges with one masked dense reduction: no scatter, no
segment map, no prefix sum.  The fused digest (DESIGN.md §4.2) packs a
whole state at tile alignment, so each tile belongs to exactly one leaf;
``ops.checksum`` is the same combine over one array.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.backend import interpret_mode

LANES = 128
TILE_ROWS = 256
TILE = TILE_ROWS * LANES  # 32768 int32 = 128 KiB per tile
BLOCK_TILES = 8           # tiles per compiled grid step: a 1 MiB block


def _row_checksum_kernel(x_ref, out_ref):
    """x_ref (g, TILE_ROWS, LANES) -> out_ref (2, g, LANES): per tile and
    lane j, Σ_r x[r, j] and Σ_r (LANES·r + j + 1)·x[r, j] (mod 2^32).  A
    tile's lanes sum to its Fletcher pair with in-tile offsets; tiles
    combine exactly: Σ(off+k)·x = off·Σx + Σk·x (mod 2^32)."""
    x = x_ref[...]
    _, rows, lanes = x.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    w = r * lanes + j + 1
    out_ref[0] = jnp.sum(x, axis=1, dtype=jnp.int32)
    out_ref[1] = jnp.sum(x * w[None], axis=1, dtype=jnp.int32)


def tile_checksums(x_i32_tiles: jnp.ndarray, *, interpret=None):
    """Single-launch whole-buffer digest pass at TILE granularity.

    x_i32_tiles : (nt, TILE_ROWS, LANES) int32 — for the fused digest,
                  every leaf packed at tile alignment (digest.DigestPlan).
    Returns (2, nt, LANES) int32 lane partials; ``combine`` folds them
    into digests.

    Compiled (TPU): one grid launch, ``BLOCK_TILES`` tiles per step.
    Interpret (CPU tests): the same kernel over the whole buffer as one
    block — the interpreter's per-grid-step cost is O(full buffer), which
    would make a tiled grid quadratic in state size.
    """
    nt = x_i32_tiles.shape[0]
    if interpret_mode(interpret):
        return pl.pallas_call(
            _row_checksum_kernel,
            out_shape=jax.ShapeDtypeStruct((2, nt, LANES), jnp.int32),
            interpret=True,
        )(x_i32_tiles)
    return _gridded_tile_checksums(x_i32_tiles, interpret=False)


def _gridded_tile_checksums(x_i32_tiles: jnp.ndarray, *, interpret: bool):
    """The compiled form of ``tile_checksums``: a grid over blocks of
    ``BLOCK_TILES`` tiles.  The last block may run past the buffer; its
    tiles past the end give partials that are cut off, and no tile's
    partials depend on another's bytes.  Tests run it interpreted at
    small ``nt`` to check it against the one-block form."""
    nt = x_i32_tiles.shape[0]
    g = min(BLOCK_TILES, nt)
    steps = -(-nt // g)
    out = pl.pallas_call(
        _row_checksum_kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec((g, TILE_ROWS, LANES),
                               lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((2, g, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, steps * g, LANES), jnp.int32),
        interpret=interpret,
    )(x_i32_tiles)
    return out[:, :nt]


def combine(partials: jnp.ndarray, first: Sequence[int],
            stop: Sequence[int]) -> jnp.ndarray:
    """(len(first), 2) int32 digests from ``tile_checksums`` partials.

    Digest i covers tiles ``[first[i], stop[i])``, whose words start at
    element 0 of tile ``first[i]``.  With T1_t, T2_t a tile's lane sums:
        s1 = Σ_t T1_t      s2 = Σ_t (T2_t + TILE·(t - first)·T1_t)
    (mod 2^32: int32 wraparound makes the combine exact).  The ranges are
    static, so the fold is one masked (digests, tiles) reduction.
    """
    t1, t2 = jnp.sum(partials, axis=2, dtype=jnp.int32)
    lo = jnp.asarray(np.asarray(first, np.int32))[:, None]
    hi = jnp.asarray(np.asarray(stop, np.int32))[:, None]
    t = jax.lax.broadcasted_iota(jnp.int32, (lo.shape[0], t1.shape[0]), 1)
    inside = (t >= lo) & (t < hi)
    s1 = jnp.sum(jnp.where(inside, t1, 0), axis=1, dtype=jnp.int32)
    s2 = jnp.sum(jnp.where(inside, t2 + (t - lo) * TILE * t1, 0), axis=1,
                 dtype=jnp.int32)
    return jnp.stack([s1, s2], axis=1)
