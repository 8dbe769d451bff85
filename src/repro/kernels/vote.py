"""Pallas TPU kernel: bitwise triple-modular-redundancy majority vote.

Repairs a corrupted replicated leaf from three synchronously-updated copies
(the tensor-level "partner induction variables" of DESIGN.md §4.2): each
output bit is the majority of the three input bits, so any single-copy
corruption — of any width, on any element — is erased.  Pure VPU bit-ops at
HBM bandwidth; tiles mirror the checksum kernel's (256, 128) int32 layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import interpret_mode

LANES = 128
TILE_ROWS = 256


def _vote_kernel(a_ref, b_ref, c_ref, out_ref):
    a = a_ref[0]
    b = b_ref[0]
    c = c_ref[0]
    out_ref[0] = (a & b) | (a & c) | (b & c)


def vote3_tiles(a, b, c, *, interpret=None):
    """a/b/c: (nt, TILE_ROWS, LANES) int32 -> majority (nt, TILE_ROWS, LANES)."""
    nt = a.shape[0]
    spec = pl.BlockSpec((1, TILE_ROWS, LANES), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _vote_kernel,
        grid=(nt,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.int32),
        interpret=interpret_mode(interpret),
    )(a, b, c)
