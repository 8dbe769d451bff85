"""Pallas TPU kernel: XOR parity fold / single-shard reconstruction.

The ICP analogue for *sharded* state (DESIGN.md §4.2): a parity shard is the
manufactured independent partner.  XOR is bit-exact — reconstruction returns
the lost shard's exact bits, so the exact-or-abort rule holds with no
floating-point caveats.  The fold walks the replica axis in VMEM-resident
(256, 128) int32 tiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import interpret_mode

LANES = 128
TILE_ROWS = 256


def _xor_fold_kernel(x_ref, out_ref):
    """x_ref: (R, 1, TILE_ROWS, LANES) — all R replicas of one tile."""
    x = x_ref[:, 0, :, :]
    R = x.shape[0]
    acc = x[0]
    for r in range(1, R):
        acc = acc ^ x[r]
    out_ref[0] = acc


def xor_fold_tiles(x, *, interpret=None):
    """x: (R, nt, TILE_ROWS, LANES) int32 -> parity (nt, TILE_ROWS, LANES)."""
    R, nt = x.shape[0], x.shape[1]
    return pl.pallas_call(
        _xor_fold_kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((R, 1, TILE_ROWS, LANES),
                               lambda i: (0, i, 0, 0))],
        out_specs=pl.BlockSpec((1, TILE_ROWS, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nt, TILE_ROWS, LANES), jnp.int32),
        interpret=interpret_mode(interpret),
    )(x)


def _xor_update_kernel(x_ref, p_ref, out_ref):
    """x_ref: (D, 1, TILE_ROWS, LANES) deltas; p_ref: the parity tile."""
    acc = p_ref[0]
    for d in range(x_ref.shape[0]):
        acc = acc ^ x_ref[d, 0]
    out_ref[0] = acc


def xor_update_tiles(x, parity, *, interpret=None):
    """Incremental parity update: ``parity ^ XOR_d x[d]``.

    ``x``: (D, nt, TILE_ROWS, LANES) int32 per-shard delta tiles
    (``old_shard XOR new_shard``), ``parity``: (nt, TILE_ROWS, LANES)
    int32 — the live parity rides the launch in place
    (``input_output_aliases``), so the steady-state update allocates
    nothing.  ``xor_update_tiles(x, zeros)`` is a rebuild-from-scratch
    fold, which is what makes incremental == rebuild testable bit-exactly
    (XOR is associative/commutative with identity 0).
    """
    D, nt = x.shape[0], x.shape[1]
    return pl.pallas_call(
        _xor_update_kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((D, 1, TILE_ROWS, LANES),
                               lambda i: (0, i, 0, 0)),
                  pl.BlockSpec((1, TILE_ROWS, LANES), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, TILE_ROWS, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nt, TILE_ROWS, LANES), jnp.int32),
        input_output_aliases={1: 0},
        interpret=interpret_mode(interpret),
    )(x, parity)
