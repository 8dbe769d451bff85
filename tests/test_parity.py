"""Unit tests for the device-resident XOR parity layer (core/parity.py
+ the ``parity_xor`` recovery rung).

Covers the PR's satellite checklist:

* incremental parity maintained through the canary's launches is
  bit-exact to a from-scratch rebuild of the same state version;
* a FINITE bit flip is localised (trial reconstruction against the
  canary's reference digest — the non-finite-only scan the seed used is
  blind to it) and repaired bit-exactly;
* a wholly LOST shard (zero-wiped, external attribution — nothing for a
  non-finite scan to see) reconstructs bit-exactly with 0 replayed
  steps and 0 host-snapshot bytes;
* two injured shards of one leaf escalate (single parity reconstructs
  exactly one);
* an uncovered-leaf-only report aborts up front;
* on a mesh: the parity slice map derives from each leaf's actual
  NamedSharding slices — a TP-sharded/DP-replicated leaf dedupes its
  replicas to unique logical blocks (XOR over an even replica count
  self-cancels), and a wiped TP slice reconstructs on every replica.
"""

import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ChecksumCanary,
    FaultReport,
    MicroCheckpointer,
    ParityStore,
    RecoveryFailed,
    RecoveryRuntime,
    inject,
    promote,
    sample_plan,
)
from repro.core.recovery_table import RUNG_PARITY


def _runtime(tiny_setup, **kw):
    cfg, state0, step, bfn = tiny_setup
    micro = MicroCheckpointer(interval=4)
    return RecoveryRuntime(step_fn=step, batch_fn=bfn,
                           iv_registry=promote(cfg, 2), micro=micro, **kw)


def _leaf(state, key):
    from repro.kernels.ops import leaf_key
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    return {leaf_key(p): v for p, v in flat}[key]


def _wipe_block(state, ps, key, blk, value=0.0):
    """Zero exactly parity block ``blk`` of ``key`` — the plan's own
    boundaries define what "one shard" means off-mesh."""
    leaf = _leaf(state, key)
    csum = np.cumsum((0,) + ps.plan.block_sizes[key])
    lo, hi = int(csum[blk]), min(int(csum[blk + 1]), leaf.size)
    flat = np.asarray(leaf).ravel().copy()
    flat[lo:hi] = value
    bad_leaf = jnp.asarray(flat.reshape(leaf.shape))

    def swap(path, x):
        from repro.kernels.ops import leaf_key
        return bad_leaf if leaf_key(path) == key else x

    return jax.tree_util.tree_map_with_path(swap, state)


def test_incremental_update_equals_rebuild(tiny_setup):
    """Parity maintained incrementally inside check_and_arm's launch over
    several steps == a from-scratch rebuild of the final state."""
    cfg, state0, step, bfn = tiny_setup
    canary = ChecksumCanary(state0, n_slices=2)
    ps = ParityStore(state0)
    ps.build(state0, 0)
    canary.attach_parity(ps)
    st = state0
    for s in range(4):
        ns, _ = step(st, bfn(s))
        assert canary.check_and_arm(s, st, ns) is None
        st = ns
    fresh = ParityStore(st)
    fresh.build(st, 4)
    assert np.array_equal(np.asarray(ps.parity), np.asarray(fresh.parity))
    assert ps.version == 4


def test_finite_flip_localized_and_repaired(tiny_setup):
    """A low-mantissa bit flip is invisible to non-finite scans; the rung
    must localise it by trial reconstruction against the canary's
    reference digest and repair bit-exactly (no snapshot, no replay)."""
    cfg, state0, step, bfn = tiny_setup
    canary = ChecksumCanary(state0, n_slices=1)
    ps = ParityStore(state0)
    ps.build(state0, 0)
    plan = dataclasses.replace(
        sample_plan(random.Random(7), state0, max_step=1, target="params"),
        bit=3)                       # finite everywhere, loss-invisible
    bad = inject(state0, plan)
    report = canary.check(0, bad)
    assert report is not None and report.leaves == ["params/" + plan.leaf]

    rt = _runtime(tiny_setup, parity=ps, canary=canary)
    fixed, ev = rt.recover(bad, report, 0, ladder=[RUNG_PARITY])
    assert ev.rung == RUNG_PARITY
    assert ev.steps_replayed == 0
    for a, b in zip(jax.tree_util.tree_leaves(fixed),
                    jax.tree_util.tree_leaves(state0)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_lost_whole_shard_reconstructs(tiny_setup):
    """A zero-wiped shard with explicit external attribution (a lost
    device's slice: nothing non-finite to scan for) reconstructs
    bit-exactly from survivors + parity."""
    cfg, state0, step, bfn = tiny_setup
    ps = ParityStore(state0)
    ps.build(state0, 0)
    key = "params/final_norm/scale"
    assert ps.covers(key)
    bad = _wipe_block(state0, ps, key, 0)
    report = FaultReport(0, "external", leaves=[key], shards={key: [0]})

    rt = _runtime(tiny_setup, parity=ps)
    fixed, ev = rt.recover(bad, report, 0, ladder=[RUNG_PARITY])
    assert ev.rung == RUNG_PARITY
    assert ev.steps_replayed == 0
    assert ev.bytes_moved > 0
    assert np.array_equal(np.asarray(_leaf(fixed, key)),
                          np.asarray(_leaf(state0, key)))


def test_two_injured_shards_escalate(tiny_setup):
    """Single parity reconstructs exactly one shard per leaf — two
    injured shards must abort the rung (exact-or-abort), not guess."""
    cfg, state0, step, bfn = tiny_setup
    ps = ParityStore(state0)
    ps.build(state0, 0)
    key = "params/embed/table"
    bad = _wipe_block(_wipe_block(state0, ps, key, 0), ps, key, 2)
    report = FaultReport(0, "external", leaves=[key], shards={key: [0, 2]})
    rt = _runtime(tiny_setup, parity=ps)
    with pytest.raises(RecoveryFailed):
        rt.recover(bad, report, 0, ladder=[RUNG_PARITY])


def test_uncovered_leaf_aborts_up_front(tiny_setup):
    """An injury attributed only to uncovered leaves (the IV block) must
    abort before any reconstruction work."""
    cfg, state0, step, bfn = tiny_setup
    ps = ParityStore(state0)
    ps.build(state0, 0)
    report = FaultReport(0, "external", leaves=["iv/step"])
    rt = _runtime(tiny_setup, parity=ps)
    with pytest.raises(RecoveryFailed):
        rt.recover(state0, report, 0, ladder=[RUNG_PARITY])


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs a multi-device mesh")
def test_tp_sharded_slice_map_regression():
    """The parity slice map must derive from each leaf's ACTUAL
    NamedSharding slices, not a first-divisible-dim guess: a TP-sharded
    (axis 1) / DP-replicated leaf has n_model unique blocks, its
    replicas collapse onto them in the device->block map, and a wiped TP
    slice reconstructs bit-exactly on EVERY replica."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.context import DistContext
    from repro.launch.mesh import make_mesh

    n = len(jax.devices())
    mesh = make_mesh((n // 2, 2), ("data", "model"))
    ctx = DistContext.for_mesh(mesh)
    leaf = jnp.arange(16 * 256, dtype=jnp.float32).reshape(16, 256)
    sh = NamedSharding(mesh, P(None, "model"))       # TP, DP-replicated
    tree = {"w": jax.device_put(leaf, sh)}
    ps = ParityStore(tree, ctx=ctx)
    ps.build(tree, 0)
    plan = ps.plan

    # dedup: 2 unique logical blocks (the model-axis halves), every data
    # replica mapped onto them
    assert plan.n_blocks["w"] == 2
    uniq, _ = plan.slices["w"]
    assert len(uniq) == 2
    dmap = plan.device_block["w"]
    assert len(dmap) == mesh.size and set(dmap) == {0, 1}
    assert len(plan.block_devices("w", 1)) == n // 2   # all replicas

    # wipe TP slice 1 (columns 128:) — materialises on every replica,
    # exactly as a logical write does
    wiped = np.asarray(leaf).copy()
    wiped[:, 128:] = 0.0
    bad = {"w": jax.device_put(jnp.asarray(wiped), sh)}
    rec = np.asarray(ps.reconstruct_shard(bad["w"], "w", 1))
    assert np.array_equal(rec, np.asarray(leaf)[:, 128:])

    # fully-replicated leaf: ONE unique block, reconstructable from the
    # parity stream alone (survivor set is empty)
    rleaf = jnp.arange(512, dtype=jnp.float32)
    rtree = {"w": jax.device_put(rleaf, NamedSharding(mesh, P(None)))}
    rps = ParityStore(rtree, ctx=ctx)
    rps.build(rtree, 0)
    assert rps.plan.n_blocks["w"] == 1
    rec = np.asarray(rps.reconstruct_shard(
        jax.device_put(jnp.zeros_like(rleaf),
                       NamedSharding(mesh, P(None))), "w", 0))
    assert np.array_equal(rec.ravel(), np.asarray(rleaf))
