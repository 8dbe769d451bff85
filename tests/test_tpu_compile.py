"""The main path's Pallas kernels, compiled for one described TPU v5e chip
at iterpro-100m's real widths.

Nothing runs: each test lowers and compiles against a v5e topology that
is described, not attached, so what the chip's compiler would refuse
(VMEM overuse, blocks off the (8, 128) tiling) fails here.  Each test
asserts that its kernel is in the compiled program as a Mosaic
``tpu_custom_call``, not interpreted.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so describing it while a
module is imported would fail every other test worker.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import checksum as ck

ARCH = "iterpro-100m"
BATCH = 4
#: the serve path's paged KV pool: block size 8, 4 slots of
#: prompt 128 + generate 32 (+1) positions
KV_BLOCK, KV_SLOTS, KV_MAX_LEN = 8, 4, 168


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def kernels_compiled(monkeypatch):
    """Kernels decide interpret-vs-compiled by JAX's default backend,
    which here is the CPU; the programs below target the described chip,
    so the backend is reported as a TPU for the test's duration."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def full_state(one_chip):
    from repro.launch.specs import state_struct
    return _on(one_chip, state_struct(get_config(ARCH), BATCH))


def _compiled_text(fn, *args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()


def test_full_state_digest_compiles(kernels_compiled, full_state, one_chip):
    """In-place pack + ``tile_checksums`` over a K=1 canary slice: every
    leaf of the full-width train state in one donated packing buffer.
    The tiles fold into leaves with no scatter, and the program's
    temporaries stay far below the packed buffer: the kernel's output is
    lane-dense, 1/128 of what it reads."""
    from repro.kernels.digest import plan_for
    plan = plan_for(full_state)
    leaves = plan.leaves(full_state)
    buf = jax.ShapeDtypeStruct((plan.n_tiles * ck.TILE,), jnp.int32,
                               sharding=one_chip)
    compiled = jax.jit(plan.digest_fn(), donate_argnums=(0,)).lower(
        buf, leaves).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "scatter" not in text
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < plan.bytes_per_pass // 8, (temps, plan.bytes_per_pass)


def test_parity_update_compiles(kernels_compiled, full_state, one_chip):
    from repro.core.parity import parity_plan_for
    from repro.kernels import parity as pk
    pplan = parity_plan_for(full_state)
    delta = jax.ShapeDtypeStruct((pplan.n_shards,) + pplan.buffer_shape,
                                 jnp.int32, sharding=one_chip)
    parity = jax.ShapeDtypeStruct(pplan.buffer_shape, jnp.int32,
                                  sharding=one_chip)
    text = _compiled_text(pk.xor_update_tiles, delta, parity, donate=(1,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kv_gather_compiles(kernels_compiled, one_chip, dtype):
    from repro.kernels.paged_kv import gather_blocks
    m = get_config(ARCH).model
    max_blocks = -(-KV_MAX_LEN // KV_BLOCK)
    pool = jax.ShapeDtypeStruct(
        (1 + KV_SLOTS * max_blocks, KV_BLOCK, m.n_layers, m.n_kv_heads,
         m.head_dim), dtype, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((KV_SLOTS, max_blocks), jnp.int32,
                                  sharding=one_chip)
    text = _compiled_text(gather_blocks, pool, tables)
    assert "tpu_custom_call" in text


def test_array_checksum_compiles(kernels_compiled, one_chip):
    """``ops.checksum`` (disk-checkpoint digests) of the embedding."""
    from repro.kernels import ops
    m = get_config(ARCH).model
    emb = jax.ShapeDtypeStruct((m.vocab_size, m.d_model), jnp.float32,
                               sharding=one_chip)
    assert "tpu_custom_call" in ops.checksum.lower(emb).compile().as_text()


def test_smoke_kernel_audit_reads_compiled_kernels(kernels_compiled,
                                                   one_chip):
    """``chip_smoke``'s audit names the Mosaic kernels of a chip program
    and flags none as interpreted."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke as cs
    x = jax.ShapeDtypeStruct((2, ck.TILE_ROWS, ck.LANES), jnp.int32,
                             sharding=one_chip)
    with cs.kernel_audit() as audit:
        jax.jit(ck.tile_checksums).lower(x).compile()
    assert cs.check_kernels(audit, {"_row_checksum_kernel"}, "v5e")
    assert not audit["interpreted"]
