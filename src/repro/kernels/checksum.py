"""Pallas TPU kernel: row-granular Fletcher-style checksum.

This is the paper's "zero-overhead detection" made real on TPU: the canary
detector must stream the full train state at HBM bandwidth with no MXU use
and negligible VMEM residency, so it can overlap with step compute.

Layout: the flat int32 view is tiled (TILE_ROWS, LANES) = (256, 128) — one
VMEM-resident tile is 128 KiB, well under the ~16 MiB/core budget, and the
lane dim matches the VPU's native 128-lane registers.

``row_checksums`` digests every 128-lane ROW of its input in one launch.
The fused digest (DESIGN.md §4.2) packs a whole train state into a single
buffer at row (512 B) alignment — tile alignment would inflate a state
with many small leaves by up to 256× — and per-leaf digests fall out of a
plain segment-sum over the row digests: no per-leaf launches, no per-leaf
host syncs.  ``ops.checksum`` is the same combine over one array.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import interpret_mode

LANES = 128
TILE_ROWS = 256
TILE = TILE_ROWS * LANES  # 32768 int32 = 128 KiB per VMEM tile


def _row_checksum_kernel(x_ref, out_ref):
    """x_ref (1, TILE_ROWS, LANES) -> out_ref (1, TILE_ROWS, 2): per-row
    Fletcher partials with lane-local weights 1..LANES.  Rows combine into
    leaf digests exactly: Σ(off+j)·x = off·Σx + Σj·x (mod 2^32)."""
    x = x_ref[0, :, :]
    rows, lanes = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1) + 1
    out_ref[0, :, 0] = jnp.sum(x, axis=1, dtype=jnp.int32)
    out_ref[0, :, 1] = jnp.sum(x * lane, axis=1, dtype=jnp.int32)


def _row_checksum_batch_kernel(x_ref, out_ref):
    """All-tiles-in-one-block variant of ``_row_checksum_kernel``:
    x_ref (nt, TILE_ROWS, LANES), out_ref (nt, TILE_ROWS, 2).

    Used in interpret mode, where per-grid-step execution costs O(full
    buffer) per step (the interpreter re-slices the whole operand each
    iteration), making a tiled grid quadratic in state size.  One block +
    vectorized reductions keeps the interpret path a single linear pass.
    Compiled TPU keeps the tiled grid (a whole train state does not fit
    VMEM)."""
    x = x_ref[...]
    _, rows, lanes = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1) + 1
    out_ref[..., 0] = jnp.sum(x, axis=2, dtype=jnp.int32)
    out_ref[..., 1] = jnp.sum(x * lane[None, :, :], axis=2, dtype=jnp.int32)


def row_checksums(x_i32_tiles: jnp.ndarray, *, interpret=None):
    """Single-launch whole-state digest pass at ROW granularity.

    x_i32_tiles : (nt, TILE_ROWS, LANES) int32 — every row of every leaf,
                  packed back to back at row (512 B) alignment
                  (see digest.DigestPlan).
    Returns (nt, TILE_ROWS, 2) int32 per-row partials; the caller combines
    rows into per-leaf digests with its static row→leaf segment map:
        leaf_s1 = Σ_r s1_r        leaf_s2 = Σ_r (s2_r + off_r·s1_r)
    where off_r is the row's element offset within its leaf (mod 2^32 —
    int32 wraparound makes the combine exact).

    Compiled (TPU): one grid launch, one 128 KiB VMEM tile per step.
    Interpret (CPU tests): the same digest as a single-block vectorized
    kernel — the interpreter's per-grid-step cost is O(full buffer), which
    would make the tiled grid quadratic in state size.
    """
    nt = x_i32_tiles.shape[0]
    if interpret_mode(interpret):
        return pl.pallas_call(
            _row_checksum_batch_kernel,
            out_shape=jax.ShapeDtypeStruct((nt, TILE_ROWS, 2), jnp.int32),
            interpret=True,
        )(x_i32_tiles)
    return _gridded_row_checksums(x_i32_tiles, interpret=False)


def _gridded_row_checksums(x_i32_tiles: jnp.ndarray, *, interpret: bool):
    """The compiled form of ``row_checksums``: a grid over tiles, one
    (TILE_ROWS, LANES) block per step.  Tests run it interpreted at small
    ``nt`` to check its semantics against the batch kernel."""
    nt = x_i32_tiles.shape[0]
    return pl.pallas_call(
        _row_checksum_kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((1, TILE_ROWS, LANES),
                               lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, TILE_ROWS, 2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nt, TILE_ROWS, 2), jnp.int32),
        interpret=interpret,
    )(x_i32_tiles)
