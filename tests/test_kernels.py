"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles.

checksum / vote / parity are bitwise algorithms -> exact equality.
flash attention is floating point -> assert_allclose with dtype tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)

SHAPES = [(7,), (128,), (4096,), (33333,), (17, 9), (128, 128), (3, 5, 7)]
DTYPES = ["float32", "bfloat16", "float16", "int32", "int8"]


def _rand(shape, dtype, key=KEY):
    if dtype in ("float32", "bfloat16", "float16"):
        return jax.random.normal(key, shape).astype(dtype)
    return jax.random.randint(key, shape, -120, 120).astype(dtype)


# ---------------------------------------------------------------------------
# checksum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_checksum_matches_ref(shape, dtype):
    x = _rand(shape, dtype)
    assert np.array_equal(np.asarray(ops.checksum(x)),
                          np.asarray(ref.checksum_ref(x)))


def test_checksum_detects_single_bit():
    from repro.core.faults import flip_bit
    x = _rand((4096,), "float32")
    for bit in (0, 7, 23, 31):
        y = flip_bit(x, 123, bit)
        assert not np.array_equal(np.asarray(ops.checksum(x)),
                                  np.asarray(ops.checksum(y)))


def test_checksum_detects_swap():
    """Position weighting: swapping two unequal elements changes s2."""
    x = jnp.arange(100, dtype=jnp.int32)
    y = x.at[3].set(x[50]).at[50].set(x[3])
    assert not np.array_equal(np.asarray(ops.checksum(x)),
                              np.asarray(ops.checksum(y)))


@pytest.mark.parametrize("nt", [1, 2, 3, 5, 8, 13])
def test_gridded_row_checksums_match_batch_kernel_and_host(nt):
    """The gridded kernel is what compiles on TPU; interpreted here at
    small ``nt`` (one partial block, whole blocks, and a ragged last
    block) it must agree lane for lane with the one-block kernel the CPU
    runs, and its tiles must combine into the host oracle's digest."""
    from repro.kernels import checksum as ck
    from repro.kernels.digest import host_checksum
    x = jax.random.randint(jax.random.PRNGKey(nt), (nt, ck.TILE_ROWS,
                           ck.LANES), -2**31, 2**31 - 1, dtype=jnp.int32)
    grid = ck._gridded_tile_checksums(x, interpret=True)
    assert grid.shape == (2, nt, ck.LANES)
    assert np.array_equal(np.asarray(grid),
                          np.asarray(ck.tile_checksums(x, interpret=True)))
    combined = ck.combine(grid, [0], [nt])[0]
    assert np.array_equal(np.asarray(combined),
                          host_checksum(np.asarray(x)))


# ---------------------------------------------------------------------------
# vote / parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(100,), (257, 3), (128, 128)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_vote3_heals_any_single_corruption(shape, dtype):
    x = _rand(shape, dtype)
    bad = jnp.asarray(x).reshape(-1).at[7].set(0).reshape(shape)
    healed = ops.vote3(bad, x, x)
    assert np.array_equal(np.asarray(healed), np.asarray(x))
    assert np.array_equal(np.asarray(ops.vote3(x, bad, x)), np.asarray(x))
    assert np.array_equal(np.asarray(ops.vote3(x, x, bad)), np.asarray(x))


@pytest.mark.parametrize("n_shards", [2, 4, 7])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_xor_reconstruct_bit_exact(n_shards, dtype):
    shards = [_rand((65, 9), dtype, jax.random.fold_in(KEY, i))
              for i in range(n_shards)]
    parity = ops.xor_fold(shards)
    for lost in range(n_shards):
        others = shards[:lost] + shards[lost + 1:]
        rec = ops.xor_reconstruct(parity, others)
        assert np.array_equal(np.asarray(rec), np.asarray(shards[lost])), \
            f"shard {lost} not reconstructed"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Sk, H, KV, D, causal, window, softcap, dtype
    (2, 128, 128, 4, 2, 32, True, 0, 0.0, "float32"),
    (1, 256, 256, 8, 8, 64, True, 64, 0.0, "float32"),
    (2, 64, 64, 4, 1, 16, True, 0, 30.0, "float32"),
    (1, 96, 96, 2, 2, 48, True, 0, 0.0, "float32"),   # non-multiple pads
    (1, 128, 128, 2, 2, 128, False, 0, 0.0, "bfloat16"),
    (1, 64, 64, 4, 4, 160, True, 0, 0.0, "float32"),  # D pads to 256
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    B, Sq, Sk, H, KV, D, causal, window, cap, dt = case
    ks = jax.random.split(jax.random.fold_in(KEY, hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D)).astype(dt)
    k = jax.random.normal(ks[1], (B, Sk, KV, D)).astype(dt)
    v = jax.random.normal(ks[2], (B, Sk, KV, D)).astype(dt)

    o = ops.flash_attention(q, k, v, causal=causal, window=window,
                            softcap=cap, block_q=32, block_k=32)

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    r = ref.flash_attention_ref(qf, kf, vf, causal=causal, window=window,
                                softcap=cap)
    r = r.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)

    tol = 3e-2 if dt == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


def test_flash_matches_model_attention():
    """The kernel agrees with the model's direct-attention path (the
    training semantics) on contiguous positions."""
    from repro.models import layers as L
    B, S, H, KV, D = 2, 64, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    pos = L.make_positions(B, S)
    direct = L.attention_direct(q, k, v, pos, pos, window=8)
    flash = ops.flash_attention(q, k, v, causal=True, window=8,
                                block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(direct),
                               atol=2e-5, rtol=2e-5)
