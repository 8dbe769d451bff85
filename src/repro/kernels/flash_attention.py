"""Pallas TPU kernel: flash attention (causal / sliding-window / GQA).

The perf-critical compute hot spot of every assigned LM architecture.  The
baseline materialises (B, H, Sq, Sk) fp32 scores (fine at 4k, impossible at
32k+); this kernel streams KV blocks through VMEM with online softmax so
live memory is O(block_q x block_k) per core.

TPU mapping
-----------
* grid = (B*H, Sq/bq, Sk/bk) — the innermost axis is ARBITRARY-ordered
  revisiting of the same output block: m/l/acc live in VMEM scratch and the
  output block is written once on the last KV block.
* BlockSpecs tile (1, bq, D) of q / (1, bk, D) of kv into VMEM; with
  bq = bk = 512 and D = 128 the working set is
  q 128 KiB + k/v 256 KiB + acc 256 KiB f32 « 16 MiB VMEM.
* matmul dims (bq, D)x(D, bk): D is a multiple of 128 for every assigned
  arch except gemma3-1b (256) and kimi (112->pad 128) — the ops wrapper
  pads D to 128 alignment so the MXU tiles cleanly.
* GQA: the kv block index is derived from the flattened (b*H + h) program
  id inside the index_map — no kv duplication in HBM.
* causal + window masks are computed from block-local iotas; fully-masked
  blocks still run (grid is static) but @pl.when skips their FLOPs.

Validated in interpret mode against ``ref.flash_attention_ref`` over a
shape/dtype sweep (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -2.0**30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, seq_k: int,
                  block_q: int, block_k: int, softcap: float):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    live = k_pos < seq_k                    # kv padding
    if causal:
        live &= q_pos >= k_pos
    if window:
        live &= (q_pos - k_pos) < window

    # skip fully-masked blocks (causal upper triangle / outside the window)
    block_live = True
    if causal:
        block_live = (jk * block_k) <= (iq * block_q + block_q - 1)
    if window:
        # newest key this q block can see is q_max; oldest is q_min-window+1
        block_live = block_live & (
            (jk * block_k + block_k - 1) > (iq * block_q - window))

    @pl.when(block_live)
    def _body():
        q = q_ref[0].astype(jnp.float32)     # (bq, D)
        k = k_ref[0].astype(jnp.float32)     # (bk, D)
        v = v_ref[0].astype(jnp.float32)     # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(live, s, _NEG_INF)

        m_prev = m_ref[...]                  # (bq,)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])      # (bq, bk)
        corr = jnp.exp(m_prev - m_new)       # (bq,)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         softcap: float = 0.0, block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret=None):
    """q (BH, Sq, D), k/v (BKV, Sk, D) pre-padded to block/lane multiples;
    BH = B*H and BKV = B*KV flattened.  Returns o (BH, Sq, D)."""
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    G = BH // BKV                      # q heads per kv head (within a batch)
    nq = Sq // block_q
    nk = Sk // block_k
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        seq_k=Sk, block_q=block_q, block_k=block_k, softcap=softcap)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),      # l (running denom)
            pltpu.VMEM((block_q, D), jnp.float32),    # acc (unnormalised o)
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
