"""Fused digest engine (kernels/digest.py + the reworked ChecksumCanary).

The detection-cost contract (DESIGN.md §4.2):
  * the fused whole-state digest is bit-identical to per-leaf ``checksum``;
  * a flipped bit in ANY leaf is attributed to exactly that leaf path;
  * the plan cache prevents retracing (trace counters stay flat);
  * one canary ``check_and_arm`` = exactly 1 fused launch + 1 host sync.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import microcheckpoint as mc
from repro.core.detect import ChecksumCanary
from repro.core.faults import flip_bit
from repro.core.microcheckpoint import MicroCheckpointer
from repro.kernels import digest as dg
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _tree():
    """Mixed dtypes/shapes: multi-tile, sub-tile, 16-bit, int, scalar."""
    ks = jax.random.split(KEY, 4)
    return {
        "params": {
            "w": jax.random.normal(ks[0], (257, 129)),          # 1+ tiles
            "b": jax.random.normal(ks[1], (33,)).astype(jnp.bfloat16),
        },
        "opt": {"m": jax.random.normal(ks[2], (40000,))},        # 2 tiles
        "iv": {"step": jnp.int32(12), "pos": jnp.int32(7)},
        "tok": jax.random.randint(ks[3], (17, 3), -5, 5, jnp.int32),
    }


def _leaves_by_key(tree):
    out = {}

    def visit(path, leaf):
        out[ops.leaf_key(path)] = leaf
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return out


# ---------------------------------------------------------------------------
# bit-exactness
# ---------------------------------------------------------------------------

def test_fused_digest_matches_per_leaf_checksum():
    tree = _tree()
    plan = dg.plan_for(tree)
    table = np.asarray(plan.digest_table(tree))
    leaves = _leaves_by_key(tree)
    assert set(plan.keys) == set(leaves)
    for i, k in enumerate(plan.keys):
        per_leaf = np.asarray(ops.checksum(leaves[k]))
        oracle = np.asarray(ref.checksum_ref(leaves[k]))
        assert np.array_equal(table[i], per_leaf), k
        assert np.array_equal(table[i], oracle), k


def test_tree_checksums_is_fused_and_bit_exact():
    tree = _tree()
    digests = ops.tree_checksums(tree)
    for k, leaf in _leaves_by_key(tree).items():
        assert np.array_equal(digests[k], np.asarray(ops.checksum(leaf))), k


def test_subtree_checksums_subset():
    tree = _tree()
    full = ops.tree_checksums(tree)
    sub = ops.subtree_checksums(tree, ["opt/m", "iv/step"])
    assert set(sub) == {"opt/m", "iv/step"}
    for k, v in sub.items():
        assert np.array_equal(v, full[k])


#: leaf sizes around the tile (32768 words): one element, not a multiple
#: of a 128-lane row, exactly one tile, several tiles with a ragged tail
TILE_SIZES = [1, 300, dg.TILE, 3 * dg.TILE + 517]


@pytest.mark.parametrize("size", TILE_SIZES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int8])
def test_tile_aligned_digest_is_bit_exact(size, dtype):
    """Each leaf's digest, packed at tile alignment beside neighbours and
    folded from its tiles' lane partials, equals the plain oracle and the
    host digest of its bytes."""
    k = jax.random.fold_in(KEY, size)
    leaf = (jax.random.normal(k, (size,)) * 50).astype(dtype)
    tree = {"a": jnp.arange(5, dtype=jnp.int32), "leaf": leaf,
            "z": jax.random.normal(KEY, (dg.TILE + 1,))}
    plan = dg.plan_for(tree)
    table = np.asarray(plan.digest_table(tree))
    for i, key in enumerate(plan.keys):
        x = _leaves_by_key(tree)[key]
        assert np.array_equal(table[i], np.asarray(ref.checksum_ref(x))), key
        assert np.array_equal(table[i], dg.host_checksum(np.asarray(x))), key
    assert np.array_equal(np.asarray(ops.checksum(leaf)), table[1])


def test_many_small_leaves_bit_exact():
    """Hundreds of sub-tile leaves of mixed dtypes: one tile each, every
    digest exact, and the buffer one tile per leaf."""
    dtypes = (jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32)
    rng = np.random.default_rng(7)
    tree = {f"l{i:03d}": jnp.asarray(rng.normal(size=1 + (37 * i) % 700) * 9,
                                     dtype=dtypes[i % 4])
            for i in range(160)}
    plan = dg.plan_for(tree)
    assert plan.n_tiles == 160
    table = np.asarray(plan.digest_table(tree))
    for i, key in enumerate(plan.keys):
        assert np.array_equal(table[i], dg.host_checksum(
            np.asarray(tree[key]))), key


def test_check_arm_union_digests_input_and_output_leaves():
    """The K=1 check-and-arm union packs every leaf of the input state and
    every leaf of the output state in one buffer: the check half compares
    the input's digests, the arm half writes the output's, both exact."""
    tree = _tree()
    new = jax.tree_util.tree_map(lambda x: x + jnp.ones_like(x), tree)
    plan = dg.plan_for(tree)
    idx = list(range(plan.n_leaves))
    core, union = dg.check_arm_subcomputation(plan, idx, idx)
    assert union == tuple(idx) * 2
    fn = jax.jit(lambda b, a, n, r, w: core(
        b, plan.leaves(a) + plan.leaves(n), r, w), donate_argnums=(0, 4))
    host_new = np.stack([dg.host_checksum(np.asarray(_leaves_by_key(new)[k]))
                         for k in plan.keys])
    ref_in = plan.digest_table(tree)
    buf, flag, bad, armed = fn(plan.take_buffer(union), tree, new, ref_in,
                               jnp.zeros_like(ref_in))
    assert not bool(flag) and not np.asarray(bad).any()
    assert np.array_equal(np.asarray(armed), host_new)
    # a stale reference row fires the flag on exactly that leaf
    m = plan.index_of("opt/m")
    stale = plan.digest_table(tree).at[m, 0].add(1)
    buf, flag, bad, armed = fn(buf, tree, new, stale,
                               jnp.zeros_like(ref_in))
    assert bool(flag)
    assert np.flatnonzero(np.asarray(bad)).tolist() == [m]
    assert np.array_equal(np.asarray(armed), host_new)


def test_canary_program_has_no_scatter():
    """The fused check-and-arm program folds tiles and arms rows without a
    scatter."""
    tree = _tree()
    plan = dg.plan_for(tree)
    idx = list(range(plan.n_leaves))
    core, union = dg.check_arm_subcomputation(plan, idx, idx)
    table = plan.digest_table(tree)
    text = jax.jit(lambda b, a, r, w: core(
        b, plan.leaves(a) + plan.leaves(a), r, w)).lower(
        plan.take_buffer(union), tree, table, table).as_text()
    assert "scatter" not in text


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def test_flip_in_any_leaf_attributed_to_exactly_that_leaf():
    tree = _tree()
    reference = ops.tree_checksums(tree)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    for j, (path, leaf) in enumerate(flat):
        key = ops.leaf_key(path)
        bit = 3 if np.asarray(leaf).dtype.itemsize * 8 > 3 else 0
        corrupted = jax.tree_util.tree_unflatten(
            treedef,
            [flip_bit(x, 0, bit) if i == j else x
             for i, (_, x) in enumerate(flat)])
        assert ops.verify_tree(corrupted, reference) == [key]


@pytest.mark.parametrize("where", ["first", "last"])
def test_flip_at_tile_edge_attributed_to_exactly_that_leaf(where):
    """A flip in the first or the last word of a tile moves only the digest
    of the leaf that owns the tile, in every leaf of several tiles."""
    k = jax.random.split(KEY, 3)
    tree = {"a": jax.random.normal(k[0], (2 * dg.TILE + 9,)),
            "b": jax.random.normal(k[1], (3 * dg.TILE,)).astype(jnp.bfloat16),
            "c": jax.random.normal(k[2], (dg.TILE,))}
    reference = ops.tree_checksums(tree)
    for key in tree:
        n = tree[key].size
        for t in range(-(-n // dg.TILE)):
            e = t * dg.TILE if where == "first" \
                else min((t + 1) * dg.TILE, n) - 1
            bad = dict(tree, **{key: flip_bit(tree[key], e, 5)})
            assert ops.verify_tree(bad, reference) == [key], (key, e)


def test_canary_names_dormant_flip_in_armed_window():
    """Corruption landing in a slice between its arm and its check — the
    window the rotating canary guards — is caught at that slice's next
    check and attributed to exactly the corrupted leaf."""
    tree = _tree()
    K = 3
    canary = ChecksumCanary(tree, n_slices=K)
    target_slice = list(canary._keys).index("opt/m") % K
    bad = dict(tree, opt={"m": flip_bit(tree["opt"]["m"], 11, 4)})
    reports = []
    for s in range(K, 2 * K):
        # the flip manifests while slice `target_slice` is armed: present
        # the corrupted state at that slice's check step
        seen = bad if s % K == target_slice else tree
        reports.append(canary.check_and_arm(s, seen))
    hits = [r for r in reports if r is not None]
    assert len(hits) == 1
    assert hits[0].leaves == ["opt/m"]


# ---------------------------------------------------------------------------
# hot-path accounting: launches / syncs / retraces
# ---------------------------------------------------------------------------

def test_check_and_arm_is_one_launch_one_sync_no_retrace():
    tree = _tree()
    assert len(jax.tree_util.tree_leaves(tree)) > 4   # multi-leaf state
    canary = ChecksumCanary(tree, n_slices=4)
    for s in range(8):                                # warm every rotation
        canary.check_and_arm(s, tree)
    dg.STATS.reset()
    for s in range(8, 16):
        assert canary.check_and_arm(s, tree) is None
    launches, syncs, traces = dg.STATS.snapshot()
    assert launches == 8     # exactly ONE fused launch per step
    assert syncs == 8        # exactly ONE device→host transfer per step
    assert traces == 0       # plan/jit caches prevent any retracing


def test_tree_checksums_one_launch_one_sync():
    tree = _tree()
    ops.tree_checksums(tree)                          # warm/compile
    dg.STATS.reset()
    ops.tree_checksums(tree)
    launches, syncs, traces = dg.STATS.snapshot()
    assert (launches, syncs, traces) == (1, 1, 0)


def test_plan_cache_reuses_plan_and_compiled_fns():
    tree = _tree()
    plan = dg.plan_for(tree)
    same_structure = jax.tree_util.tree_map(lambda x: x + 0, tree)
    assert dg.plan_for(same_structure) is plan
    plan.digest_table(tree)                           # warm
    dg.STATS.reset()
    plan.digest_table(same_structure)                 # same structure ->
    assert dg.STATS.traces == 0                       # no retrace
    # a different structure gets its own plan
    other = {"x": jnp.ones((5,))}
    assert dg.plan_for(other) is not plan


def test_canary_instances_share_compiled_step_fns():
    """One canary per campaign trial must not recompile the fused step."""
    tree = _tree()
    c1 = ChecksumCanary(tree, n_slices=2)
    for s in range(4):
        c1.check_and_arm(s, tree)
    dg.STATS.reset()
    c2 = ChecksumCanary(tree, n_slices=2)             # fresh instance
    for s in range(4):
        c2.check_and_arm(s, tree)
    assert dg.STATS.traces == 0


# ---------------------------------------------------------------------------
# consumers
# ---------------------------------------------------------------------------

def test_micro_snapshot_single_pass_digests_and_cached_memory():
    tree = _tree()
    micro = MicroCheckpointer(interval=1, keep=2)
    micro.snapshot(0, tree)
    snap = micro.snapshots[-1]
    # digests certify the stored bytes and match the live state's digests
    assert micro.verify(snap, jax.device_put(snap.state)) == []
    live = ops.tree_checksums(tree)
    assert all(np.array_equal(snap.digests[k], live[k]) for k in live)
    # memory accounting cached at snapshot time, no re-materialisation
    want = sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))
    assert snap.nbytes == want
    micro.snapshot(1, tree)
    assert micro.memory_bytes == 2 * want


def test_refresh_subset_updates_reference_rows():
    tree = _tree()
    canary = ChecksumCanary(tree, n_slices=1)
    bad = dict(tree, opt={"m": flip_bit(tree["opt"]["m"], 2, 8)})
    assert canary.check(0, bad) is not None
    canary.refresh(bad, keys=["opt/m"])
    assert canary.check(0, bad) is None
    # and the rest of the table still guards the untouched leaves
    worse = dict(bad, tok=flip_bit(bad["tok"], 1, 0))
    report = canary.check(0, worse)
    assert report is not None and report.leaves == ["tok"]


def test_partial_refresh_keeps_generation_and_unrelated_slices():
    """Regression for the partial-refresh contract (see
    ``ChecksumCanary.refresh``): an explicit ``keys=`` refresh must NOT
    bump the generation and must not invalidate any other slice's armed
    reference.  A generation bump here would swap the read/write roles of
    the double-buffered pair mid-rotation, so the donated pair's next
    ``check`` would verify a slice against rows armed two generations ago
    (an older state version) and fire a spurious fault."""
    tree = _tree()
    canary = ChecksumCanary(tree, n_slices=3)
    step = _toy_step()

    # donated-style pair over a MUTATING state: every check verifies the
    # same version the matching arm digested
    state = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), tree)
    for s in range(3):
        canary.arm_current(s, state)
        assert canary.check(s, state) is None
        state = step(state)

    gen = canary.generation
    canary.arm_current(3, state)
    # mid-generation targeted repair of ONE leaf (its row is patched in
    # both tables; nothing else may change)
    canary.refresh(state, keys=["opt/m"])
    assert canary.generation == gen + 1  # only arm_current's own bump
    # the pending slice's armed reference must still verify, and the
    # following full rotation must stay trap-free
    assert canary.check(3, state) is None
    state = step(state)
    for s in range(4, 7):
        canary.arm_current(s, state)
        assert canary.check(s, state) is None, s
        state = step(state)


# ---------------------------------------------------------------------------
# donation contract: the resilient hot path survives donate_argnums
# ---------------------------------------------------------------------------

def _toy_step():
    """Structure/dtype-preserving donated step over ``_tree()`` states."""
    def upd(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return (x * jnp.asarray(1.01, x.dtype)).astype(x.dtype)
        return x + jnp.ones((), x.dtype)
    return jax.jit(lambda t: jax.tree_util.tree_map(upd, t),
                   donate_argnums=(0,))


def _host_leaves(tree):
    # copy via a device temp: converting the live array to numpy can
    # cache a host view on it and silently veto the donation this test
    # asserts (see microcheckpoint._host_copy)
    return {k: np.asarray(jnp.array(v, copy=True))
            for k, v in _leaves_by_key(tree).items()}


def test_donated_step_deletes_prestep_and_digests_survive():
    """The core donation contract: after the donated step consumes the
    pre-step buffers, (a) they are really gone (``is_deleted``), and
    (b) their digests — armed at the buffer's last readable moment —
    survive in the read-generation table, bit-identical to the per-leaf
    oracle of the (now unreachable) pre-step bytes."""
    state = _tree()
    dstep = _toy_step()
    K = 2
    canary = ChecksumCanary(state, n_slices=K)
    for s in range(2 * K):
        # donated pair: arm slice s%K, verify the same slice/version
        canary.arm_current(s, state)
        host = _host_leaves(state)          # oracle copy, survives donation
        assert canary.check(s, state) is None
        old_leaves = jax.tree_util.tree_leaves(state)
        state = dstep(state)
        # (a) the pre-step buffer is deleted — donation really happened
        assert all(l.is_deleted() for l in old_leaves)
        # (b) the armed digests outlive it, bit-identical to the oracle
        surviving = canary.reference_digests()
        for i in canary._slice_indices(s):
            key = canary._keys[i]
            assert np.array_equal(surviving[key],
                                  np.asarray(ref.checksum_ref(host[key]))), key


def test_donated_pair_hot_path_accounting():
    """Steady-state donated step: arm = 1 launch + 0 syncs, check =
    1 launch + 1 scalar sync (the per-call 1-launch/1-sync contract), no
    retraces, and the packing buffers are pointer-stable (zero new
    steady-state allocations on the digest path)."""
    state = _tree()
    dstep = _toy_step()
    K = 4
    canary = ChecksumCanary(state, n_slices=K)
    for s in range(K):                       # warm every rotation
        canary.arm_current(s, state)
        canary.check(s, state)
        state = dstep(state)
    ptrs = {idx: canary.plan.buffer_pointer(idx)
            for idx in list(canary.plan._pack_bufs)}
    state = dstep(state)                     # flush pointer-probe residue
    dg.STATS.reset()
    n = 2 * K
    for s in range(K, K + n):
        canary.arm_current(s, state)
        assert canary.check(s, state) is None
        state = dstep(state)
    launches, syncs, traces = dg.STATS.snapshot()
    assert launches == 2 * n     # arm + check, each ONE fused launch
    assert syncs == n            # ONLY the check syncs, one scalar
    assert traces == 0           # plan/jit caches prevent any retracing
    for idx, p in ptrs.items():  # same HBM ranges rewritten in place
        assert canary.plan.buffer_pointer(idx) == p, idx


def test_donated_flip_between_arm_and_check_is_attributed():
    """Corruption landing after the arm and before the step consumes the
    buffer — the donated protocol's guarded window — is caught by the
    check at the buffer's last readable moment and attributed to exactly
    the corrupted leaf, before the step can consume the rot."""
    state = _tree()
    dstep = _toy_step()
    canary = ChecksumCanary(state, n_slices=1)
    reports = []
    for s in range(4):
        canary.arm_current(s, state)
        seen = state
        if s == 2:                            # the adversary window
            seen = dict(state, opt={"m": flip_bit(state["opt"]["m"], 11, 4)})
        reports.append(canary.check(s, seen))
        state = dstep(seen)
    hits = [r for r in reports if r is not None]
    assert len(hits) == 1
    assert hits[0].leaves == ["opt/m"]


def test_full_refresh_bumps_generation_and_survives_restore():
    """Regression (donation + restore): a full ``refresh`` must BUMP the
    table generation so the fresh digests become the read generation —
    without the bump the first post-restore check under donation verifies
    the restored state against the stale pre-restore generation and fires
    a spurious checksum fault."""
    state = _tree()
    dstep = _toy_step()
    K = 2
    canary = ChecksumCanary(state, n_slices=K)
    restore_point = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                           state)
    for s in range(2 * K):                    # advance the donated loop
        canary.arm_current(s, state)
        assert canary.check(s, state) is None
        state = dstep(state)

    # cold restore to the step-0 state: the tables hold digests of a
    # far-future generation until refresh installs the restored digests
    state = restore_point
    g0 = canary.generation
    canary.refresh(state)
    assert canary.generation > g0             # the load-bearing bump
    # first post-restore check must NOT fire spuriously...
    assert canary.check(0, state) is None
    # ...the donated pair protocol resumes cleanly...
    for s in range(K):
        canary.arm_current(s, state)
        assert canary.check(s, state) is None
        state = dstep(state)
    # ...and a real flip is still caught and attributed ("tok" is an
    # odd-index plan leaf, so an odd step's slice covers it)
    bad = dict(state, tok=flip_bit(state["tok"], 1, 0))
    s = K + 1
    assert canary.plan.index_of("tok") % K == s % K
    canary.arm_current(s, state)
    report = canary.check(s, bad)
    assert report is not None and report.leaves == ["tok"]


# ---------------------------------------------------------------------------
# host digest path: snapshot certification without device re-upload
# ---------------------------------------------------------------------------

def test_host_checksum_matches_oracle_all_dtypes():
    key = jax.random.PRNGKey(3)
    arrays = [
        jax.random.normal(key, (129, 7)),                     # f32, odd
        jax.random.normal(key, (33,)).astype(jnp.bfloat16),   # bf16
        jax.random.normal(key, (5, 5)).astype(jnp.float16),   # f16
        jnp.arange(-7, 9, dtype=jnp.int32),                   # i32
        jnp.arange(-4, 5, dtype=jnp.int8),                    # i8
        jnp.int32(42),                                        # scalar
    ]
    for a in arrays:
        host = np.asarray(a)
        assert np.array_equal(dg.host_checksum(host),
                              np.asarray(ref.checksum_ref(a))), a.dtype


@pytest.mark.parametrize("cap", [None, 64 << 10], ids=["whole", "split"])
def test_snapshot_digests_are_device_side_and_bit_exact(cap, monkeypatch):
    """A snapshot certifies the live state on the device: the stored
    digests equal the host oracle over the stored copy and the device
    engine over the live tree, for one fetch and one launch per leaf
    group, and a second snapshot retraces nothing.  Under a cap smaller
    than ``opt/m`` that leaf forms a group alone and still digests
    bit-identically."""
    if cap is not None:
        monkeypatch.setattr(mc, "DIGEST_GROUP_BYTES", cap)
    tree = _tree()
    live = ops.tree_checksums(tree)
    micro = MicroCheckpointer(interval=1)
    with obs.span("probe.mark") as mark:
        pass
    dg.STATS.reset()
    micro.snapshot(0, tree)
    launches, syncs, _ = dg.STATS.snapshot()
    digest = [r for r in obs.records()
              if r.id > mark.id and r.name == "snapshot.digest"]
    assert len(digest) == 1
    groups = digest[0].attrs["groups"]
    assert syncs == 1 and launches == groups
    assert digest[0].attrs["bytes"] >= 4 * sum(
        np.size(x) for x in jax.tree_util.tree_leaves(tree))
    snap = micro.snapshots[-1]
    assert set(snap.digests) == set(live)
    for k, leaf in _leaves_by_key(snap.state).items():
        assert np.array_equal(snap.digests[k], dg.host_checksum(leaf)), k
        assert np.array_equal(snap.digests[k], live[k]), k
    plan = dg.plan_for(tree)
    if cap is None:
        assert groups == 1
    else:
        m = plan.index_of("opt/m")
        assert plan.specs[m].n_rows * dg.LANES * 4 > cap
        assert (m,) in mc.digest_groups(plan)[0] and groups > 2
    dg.STATS.reset()
    micro.snapshot(1, tree)
    assert dg.STATS.traces == 0


def test_mesh_snapshots_keep_host_digests():
    """With a mesh context the snapshot stays on the host path: per-leaf
    and per-shard host digests of the copy, no device digest launched or
    fetched, and a rotted copy is named by the host re-digest."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.context import DistContext
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = jax.device_put(_tree(), NamedSharding(mesh, P()))
    micro = MicroCheckpointer(interval=1, ctx=DistContext.for_mesh(mesh))
    dg.STATS.reset()
    micro.snapshot(0, tree)
    assert dg.STATS.snapshot()[:2] == (0, 0)
    snap = micro.snapshots[-1]
    leaves = _leaves_by_key(snap.state)
    assert set(snap.shard_digests) == set(snap.digests) == set(leaves)
    for k, leaf in leaves.items():
        assert np.array_equal(snap.digests[k], dg.host_checksum(leaf)), k
        assert np.array_equal(snap.shard_digests[k][0], snap.digests[k]), k
    assert micro.verify(snap, tree) == []
    rotted = np.array(leaves["opt/m"])
    rotted[5] = -rotted[5]
    snap.state = dict(snap.state, opt={"m": rotted})
    assert micro.verify(snap, tree) == ["opt/m"]
    assert dg.STATS.snapshot()[:2] == (0, 0)


# ---------------------------------------------------------------------------
# the same combine, per device, on a mesh (8 CPU devices in a child process:
# the test session keeps one device)
# ---------------------------------------------------------------------------

SHARDED_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.faults import flip_bit
    from repro.kernels import digest as dg
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    host = {"w": jax.random.normal(k[0], (256, 300)),
            "e": jax.random.normal(k[1], (8, dg.TILE + 10)).astype(
                jnp.bfloat16),
            "b": jax.random.randint(k[2], (300,), -9, 9).astype(jnp.int8),
            "s": jnp.int32(7)}
    specs = {"w": P("data", "model"), "e": P("data", None), "b": P(),
             "s": P()}
    put = lambda t: {n: jax.device_put(x, NamedSharding(mesh, specs[n]))
                     for n, x in t.items()}
    tree = put(host)
    plan = dg.sharded_plan_for(tree, mesh)
    assert isinstance(plan, dg.ShardedDigestPlan)
    assert plan.n_shards == 8
    table = np.asarray(plan.digest_table(tree))
    assert table.shape == (8, plan.n_leaves, 2)
    for i, key in enumerate(plan.keys):
        assert np.array_equal(table[:, i],
                              dg.host_shard_checksums(tree[key])), key
    # check the input, arm the output, in one shard_map'd launch
    new = put({n: x + jnp.ones_like(x) for n, x in host.items()})
    idx = list(range(plan.n_leaves))
    core, union = dg.check_arm_subcomputation(plan, idx, idx)
    fn = jax.jit(lambda b, a, n, r, w: core(
        b, plan.leaves(a) + plan.leaves(n), r, w))
    ref = plan.digest_table(tree)
    buf, flag, bad, armed = fn(plan.take_buffer(union), tree, new, ref, ref)
    assert not bool(flag) and not np.asarray(bad).any()
    want = np.stack([dg.host_shard_checksums(new[key]) for key in plan.keys],
                    axis=1)
    assert np.array_equal(np.asarray(armed), want)
    # a flip in the first word of shard 5's block of "w" names (5, "w")
    j = plan.index_of("w")
    w = np.array(host["w"])
    r0, c0 = [sl.start or 0 for sl in dg.shard_indices(tree["w"])[5]]
    w[r0, c0] = np.asarray(flip_bit(jnp.float32(w[r0, c0]), 0, 9))
    hit = put(dict(host, w=jnp.asarray(w)))
    buf, flag, bad, _ = fn(buf, hit, new, ref, ref)
    assert bool(flag)
    assert np.argwhere(np.asarray(bad)).tolist() == [[5, j]]
    print("SHARDED_OK")
""")


def test_sharded_plan_reuses_the_combine_on_an_8_device_mesh():
    """``ShardedDigestPlan`` runs the single-device digest core per device:
    every (shard, leaf) digest equals the host digest of that shard's
    bytes, the fused check-and-arm is exact, and a flip names its shard."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", SHARDED_PROG],
                         capture_output=True, text=True, cwd=repo,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
