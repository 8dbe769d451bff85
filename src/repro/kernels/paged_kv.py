"""Pallas TPU kernel: paged-KV block gather (serving engine, DESIGN.md §6).

The paged serving engine keeps every decode-cache leaf as a shared **block
pool** ``(n_blocks, block_size, ...)`` plus per-slot block tables
``(S, max_blocks)`` — a slot owns exactly the blocks its sequence needs, so
heterogeneous prompt/generation lengths stop paying ``max_len`` HBM per
slot.  The decode hot path then needs one data movement: materialise each
slot's owned blocks as a contiguous per-slot view for the vmapped decode
step.  That gather is this kernel.

Why a gather (and not a fused paged-attention kernel): the engine's
resilience contract demands the paged engine be **bit-exact** against the
dense slot-major engine (tests/test_serving.py), and a fused online-softmax
paged-attention kernel would change the floating-point reduction order.
Gathering the owned blocks and running the *unmodified* dense decode on the
gathered view keeps the computation literally identical — same ops over the
same values — so bit-exactness holds by construction, and the canary /
replay machinery needs no numeric caveats.

TPU mapping
-----------
* grid = (S, max_blocks): one program per (slot, logical block).
* The block table rides ``PrefetchScalarGridSpec`` **scalar prefetch**: the
  input BlockSpec's index_map reads ``bt[s, j]`` to pick which *physical*
  pool block is DMA'd into VMEM — the kernel body is a pure copy, so the
  whole gather is HBM->HBM DMA traffic steered by the table, touching only
  the blocks a slot owns (plus the scratch block for unallocated entries).
* Block shape (1, block_size, F) where F flattens the per-token feature
  dims; for compiled TPU lowering F should be a multiple of 128 lanes (the
  iterpro smoke config's F = count*KV*D = 128 is; CPU interpret mode has no
  constraint).

Validated against the jnp reference gather over shape/dtype sweeps in
tests/test_kernels.py, and load-bearing in the serving engine's fused step
(one combined launch: gather + vmapped decode + scatter-back + canary).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode


def gather_blocks(pool_leaf, block_tables, *, interpret=None):
    """Gather each slot's owned blocks out of a shared block pool.

    pool_leaf    : (n_blocks, block_size, *feat) — one cache leaf's pool
    block_tables : (S, max_blocks) int32 — physical block id per (slot,
                   logical block); unallocated entries point at the scratch
                   block 0 (the caller masks those positions out of
                   attention, so their bytes are never consumed).

    Returns (S, max_blocks, block_size, *feat): slot-major, logical-block
    ordered — ``out[s].reshape(max_blocks * block_size, *feat)`` is slot
    ``s``'s linear cache view.
    """
    nb, bs = pool_leaf.shape[:2]
    feat = pool_leaf.shape[2:]
    F = int(np.prod(feat, dtype=np.int64)) if feat else 1
    S, mb = block_tables.shape
    pool3 = pool_leaf.reshape(nb, bs, F)

    def _gather_block_kernel(bt_ref, pool_ref, out_ref):
        del bt_ref  # consumed by the index_map, not the body
        out_ref[0, 0] = pool_ref[0]

    out = pl.pallas_call(
        _gather_block_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, mb),
            in_specs=[
                pl.BlockSpec((1, bs, F), lambda s, j, bt: (bt[s, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bs, F),
                                   lambda s, j, bt: (s, j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((S, mb, bs, F), pool_leaf.dtype),
        interpret=interpret_mode(interpret),
    )(block_tables, pool3)
    return out.reshape((S, mb, bs) + feat)


def gather_blocks_ref(pool_leaf, block_tables):
    """jnp reference gather (oracle for the kernel; also the admission-path
    context gather, where one slot's blocks are fetched off the hot path)."""
    return jnp.take(pool_leaf, block_tables, axis=0)
