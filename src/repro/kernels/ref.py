"""Pure-jnp oracles for the Pallas kernels.

These define the *semantics*; the kernels must match them bit-exactly
(all the algorithms are integer/bitwise, so there is no tolerance — tests
assert equality, not allclose).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def to_i32(x) -> jnp.ndarray:
    """Bit-cast any array to a flat int32 vector (zero-padded to 4-byte
    multiples).  The checksum domain is raw bits, so repairs can be verified
    bit-exactly regardless of dtype."""
    x = jnp.asarray(x)
    if x.dtype == jnp.int32:
        flat = x.reshape(-1)
    elif x.dtype in (jnp.float32, jnp.uint32):
        flat = jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1)
    elif x.dtype in (jnp.bfloat16, jnp.float16, jnp.int16, jnp.uint16):
        i16 = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.int16)
        flat = i16.astype(jnp.uint16).astype(jnp.int32)
    elif x.dtype in (jnp.int8, jnp.uint8):
        flat = x.reshape(-1).astype(jnp.uint8).astype(jnp.int32)
    elif x.dtype == jnp.int64:
        flat = x.reshape(-1).astype(jnp.int32)
    else:
        flat = jax.lax.bitcast_convert_type(
            x.astype(jnp.float32), jnp.int32).reshape(-1)
    return flat


def checksum_ref(x) -> jnp.ndarray:
    """Fletcher-style two-term digest over the raw bits of ``x``.

    s1 = Σ x_i               (mod 2^32, int32 wraparound)
    s2 = Σ (i+1)·x_i         (mod 2^32)
    Returns int32[2].  Position weighting catches element swaps that a plain
    sum would miss.
    """
    flat = to_i32(x)
    n = flat.shape[0]
    idx = (jnp.arange(n, dtype=jnp.int32) + 1)
    s1 = jnp.sum(flat, dtype=jnp.int32)
    s2 = jnp.sum(flat * idx, dtype=jnp.int32)
    return jnp.stack([s1, s2])


def vote3_ref(a, b, c):
    """Bitwise triple-modular-redundancy majority: out bit = majority bit."""
    ai, bi, ci = (to_i32(v) for v in (a, b, c))
    maj = (ai & bi) | (ai & ci) | (bi & ci)
    return from_i32(maj, a)


def xor_fold_ref(arrays):
    """XOR-fold of equal-shaped arrays (parity construction)."""
    acc = to_i32(arrays[0])
    for a in arrays[1:]:
        acc = acc ^ to_i32(a)
    return from_i32(acc, arrays[0])


def xor_reconstruct_ref(parity, others):
    """Reconstruct the missing shard: parity ^ xor(others)."""
    acc = to_i32(parity)
    for a in others:
        acc = acc ^ to_i32(a)
    return from_i32(acc, parity)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0):
    """Dense-softmax oracle for the flash kernel.

    q (BH, Sq, D), k/v (BKV, Sk, D), BH a multiple of BKV (GQA flattening).
    fp32 softmax, same masking semantics as the kernel.
    """
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    G = BH // BKV
    kr = jnp.repeat(k, G, axis=0)
    vr = jnp.repeat(v, G, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) / np.sqrt(D)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    live = jnp.ones((Sq, Sk), bool)
    if causal:
        live &= qp >= kp
    if window:
        live &= (qp - kp) < window
    s = jnp.where(live[None], s, -2.0**30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      vr.astype(jnp.float32)).astype(q.dtype)


def from_i32(flat_i32, like) -> jnp.ndarray:
    """Inverse of to_i32 for the dtypes used in state trees."""
    like = jnp.asarray(like)
    if like.dtype == jnp.int32:
        return flat_i32.reshape(like.shape)
    if like.dtype in (jnp.float32, jnp.uint32):
        return jax.lax.bitcast_convert_type(
            flat_i32.reshape(like.shape), like.dtype)
    if like.dtype in (jnp.bfloat16, jnp.float16, jnp.int16, jnp.uint16):
        i16 = flat_i32.astype(jnp.uint16).astype(jnp.int16)
        return jax.lax.bitcast_convert_type(
            i16.reshape(like.shape), like.dtype)
    if like.dtype in (jnp.int8, jnp.uint8):
        return flat_i32.astype(like.dtype).reshape(like.shape)
    raise TypeError(f"unsupported dtype {like.dtype}")
