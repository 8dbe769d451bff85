"""In-step fused detection (core/fused_step.py + ChecksumCanary.fuse_into_step).

The PR-4 tentpole contract (DESIGN.md §4.2, "in-step fused" column):
  * the fused step's trajectory AND its digests are bit-identical to the
    PR-3 paths (non-donated ``check_and_arm`` and the donated
    ``arm_current``/``check`` pair) — fusing detection into the step must
    not change a single bit of either;
  * steady state is exactly 1 combined launch + 1 scalar sync per step,
    zero retraces (the K-executable cache holds, across factory
    instances too);
  * an injected flip is attributed to exactly the corrupted leaf via the
    DEFERRED resolver (the hot path fetched only the scalar flag);
  * donation really happens (pre-step buffers die) and the armed digests
    outlive them, bit-identical to the per-leaf oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.detect import ChecksumCanary, FaultReport
from repro.core.faults import flip_bit
from repro.kernels import digest as dg
from repro.kernels import ref

KEY = jax.random.PRNGKey(11)


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


def _raw_step(t, batch):
    """Structure/dtype-preserving step over ``_tree()`` states (+aux)."""
    def upd(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return (x * jnp.asarray(1.01, x.dtype)).astype(x.dtype)
        return x + jnp.ones((), x.dtype)
    return jax.tree_util.tree_map(upd, t), {"loss": batch.sum()}


BATCH = jnp.ones((8,), jnp.float32)


def _host(tree_or_leaf):
    """Host copy via a device temp: a zero-copy ``np.asarray`` view would
    pin the live buffer and silently veto the next donation (the PR-3
    footgun this suite must not trip)."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jnp.array(x, copy=True)), tree_or_leaf)


def _same_tree(a, b) -> bool:
    return all(np.array_equal(x, y)
               for x, y in zip(jax.tree_util.tree_leaves(_host(a)),
                               jax.tree_util.tree_leaves(_host(b))))


# ---------------------------------------------------------------------------
# bit-exact conformance with the PR-3 paths
# ---------------------------------------------------------------------------

def test_fused_matches_check_and_arm_bitwise_nondonated():
    """Fused (donate=False) vs the non-donated ``check_and_arm`` path:
    identical protocol timing (check slice s of input, arm slice s+1 of
    output), so trajectories AND reference tables must match bit for
    bit at every step."""
    K = 3
    state_f = _tree()
    can_f = ChecksumCanary(state_f, n_slices=K)
    fac = can_f.fuse_into_step(_raw_step, donate=False)

    state_r = _tree()
    can_r = ChecksumCanary(state_r, n_slices=K)
    jstep = jax.jit(_raw_step)

    for s in range(2 * K):
        state_f, _, rep = fac.step(s, state_f, BATCH)
        assert rep is None
        new_r, _ = jstep(state_r, BATCH)
        assert can_r.check_and_arm(s, state_r, new_r) is None
        state_r = new_r
        assert _same_tree(state_f, state_r), f"trajectory diverged at {s}"
        assert np.array_equal(_host(can_f.reference),
                              _host(can_r.reference)), f"tables diverged at {s}"
        assert can_f.generation == can_r.generation


def test_fused_matches_donated_pair_bitwise():
    """Fused (donate=True) vs the PR-3 donated ``arm_current``/``check``
    pair: same trajectory bit for bit, and the digests each protocol
    verifies per step are digests of the same buffer versions — the pair
    arms slice s at step s, the fused step armed it at step s-1, so both
    must hold the per-leaf oracle digests of the same bytes."""
    K = 2
    state_f = _tree()
    can_f = ChecksumCanary(state_f, n_slices=K)
    fac = can_f.fuse_into_step(_raw_step, donate=True)

    state_r = _tree()
    can_r = ChecksumCanary(state_r, n_slices=K)
    dstep = jax.jit(_raw_step, donate_argnums=(0,))

    for s in range(2 * K):
        # oracle digests of the INPUT version both protocols will verify
        oracle = {k: np.asarray(ref.checksum_ref(jnp.array(v, copy=True)))
                  for k, v in zip(can_f._keys, can_f.plan.leaves(state_f))}

        old_f = jax.tree_util.tree_leaves(state_f)
        state_f, _, rep = fac.step(s, state_f, BATCH)
        assert rep is None
        assert all(l.is_deleted() for l in old_f), "fused donation vetoed"
        # the slice the fused step just checked was armed (at s-1, or at
        # init) with the oracle digests of the input version
        surviving = {k: t for k, t in zip(can_f._keys,
                                          _host(can_f._tables[(can_f._gen - 1) & 1]))}
        for i in can_f._slice_indices(s):
            key = can_f._keys[i]
            assert np.array_equal(surviving[key], oracle[key]), (s, key)

        can_r.arm_current(s, state_r)
        assert can_r.check(s, state_r) is None
        old_r = jax.tree_util.tree_leaves(state_r)
        state_r, _ = dstep(state_r, BATCH)
        assert all(l.is_deleted() for l in old_r), "pair donation vetoed"

        assert _same_tree(state_f, state_r), f"trajectory diverged at {s}"


# ---------------------------------------------------------------------------
# hot-path accounting + K-executable cache
# ---------------------------------------------------------------------------

def test_fused_steady_state_one_launch_one_sync_no_retrace():
    state = _tree()
    K = 4
    can = ChecksumCanary(state, n_slices=K)
    fac = can.fuse_into_step(_raw_step, donate=True)
    for s in range(K):                        # lazy warm: one full rotation
        state, _, rep = fac.step(s, state, BATCH)
        assert rep is None
    assert fac.n_compiles == K
    dg.STATS.reset()
    n = 2 * K
    for s in range(K, K + n):
        state, _, rep = fac.step(s, state, BATCH)
        assert rep is None
    launches, syncs, traces = dg.STATS.snapshot()
    assert launches == n     # ONE combined launch per step
    assert syncs == n        # ONE scalar device→host transfer per step
    assert traces == 0       # the K-executable cache holds
    assert fac.n_compiles == K                # nothing recompiled


def test_eager_warm_compiles_all_k_without_stepping():
    state = _tree()
    K = 3
    can = ChecksumCanary(state, n_slices=K)
    fac = can.fuse_into_step(_raw_step, donate=True, warm="eager")
    wall = fac.warm(state, BATCH)
    assert fac.n_compiles == K and wall > 0.0
    assert fac.compile_seconds > 0.0
    assert fac.warm(state, BATCH) == 0.0      # idempotent per signature
    g0 = can.generation                       # warm ran NO step: table and
    assert g0 == 0                            # generation untouched
    dg.STATS.reset()
    for s in range(2 * K):
        state, _, rep = fac.step(s, state, BATCH)
        assert rep is None
    assert dg.STATS.traces == 0               # warm really compiled all K
    assert fac.n_compiles == K


def test_executable_cache_shared_across_factories():
    """One factory per campaign trial must not recompile: the executable
    cache is keyed by (plan, K, step_fn, donate, rotation, args)."""
    K = 2
    state = _tree()
    can1 = ChecksumCanary(state, n_slices=K)
    fac1 = can1.fuse_into_step(_raw_step, donate=False)
    for s in range(K):
        state, _, _ = fac1.step(s, state, BATCH)
    state2 = _tree()
    can2 = ChecksumCanary(state2, n_slices=K)  # fresh canary, same plan
    fac2 = can2.fuse_into_step(_raw_step, donate=False)
    dg.STATS.reset()
    for s in range(K):
        state2, _, rep = fac2.step(s, state2, BATCH)
        assert rep is None
    assert dg.STATS.traces == 0
    assert fac2.n_compiles == 0               # global cache hit for all K


# ---------------------------------------------------------------------------
# fault path: deferred attribution
# ---------------------------------------------------------------------------

def test_fused_flip_attributed_to_exact_leaf_via_resolver():
    """A flip landing in the guarded window is detected by the in-step
    check at the slice's next rotation; the report carries only the
    scalar verdict until ``resolve()`` fetches the bad-mask vector and
    names exactly the corrupted leaf."""
    state = _tree()
    can = ChecksumCanary(state, n_slices=1)
    fac = can.fuse_into_step(_raw_step, donate=False)
    state, _, rep = fac.step(0, state, BATCH)
    assert rep is None
    bad = dict(state, opt={"m": flip_bit(state["opt"]["m"], 11, 4)})
    _, _, rep = fac.step(1, bad, BATCH)
    assert isinstance(rep, FaultReport) and rep.detector == "checksum"
    assert rep.leaves == []                   # hot path: flag only
    assert rep.resolve() == ["opt/m"]         # fault path: exact leaf
    assert rep.leaves == ["opt/m"]
    assert rep.resolve() == ["opt/m"]         # idempotent


def test_fused_donated_flip_detected_and_recovery_refresh_resumes():
    """Donated fused loop: a flip is detected in-step; after the (mock)
    recovery installs a clean state, ``refresh`` bumps the generation and
    the fused protocol resumes without spurious faults — and still
    catches the next real flip."""
    state = _tree()
    K = 2
    can = ChecksumCanary(state, n_slices=K)
    fac = can.fuse_into_step(_raw_step, donate=True)
    restore = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                     state)
    for s in range(2 * K):
        state, _, rep = fac.step(s, state, BATCH)
        assert rep is None

    def advance_to_rotation(state, s, idx):
        """Step the fused loop contiguously until the NEXT step's check
        slice covers plan leaf ``idx`` (skipping steps would leave stale
        armed slices and a false positive)."""
        while s % K != idx % K:
            state, _, rep = fac.step(s, state, BATCH)
            assert rep is None
            s += 1
        return state, s

    # adversary: flip a leaf of the live state just before the step whose
    # check slice covers it
    i = can.plan.index_of("opt/m")
    state, s = advance_to_rotation(state, 2 * K, i)
    bad = dict(state, opt={"m": flip_bit(state["opt"]["m"], 3, 7)})
    _, _, rep = fac.step(s, bad, BATCH)
    assert rep is not None and rep.resolve() == ["opt/m"]

    # recovery pivot (donated): discard the corrupt-derived output,
    # restore the snapshot, refresh the canary — the generation bump
    # makes the fresh digests the read generation
    g0 = can.generation
    state = restore
    can.refresh(state)
    assert can.generation > g0
    for s in range(2 * K):
        state, _, rep = fac.step(s, state, BATCH)
        assert rep is None                    # no spurious post-restore trap

    j = can.plan.index_of("tok")
    state, s = advance_to_rotation(state, 2 * K, j)
    bad = dict(state, tok=flip_bit(state["tok"], 1, 0))
    _, _, rep = fac.step(s, bad, BATCH)
    assert rep is not None and rep.resolve() == ["tok"]


def test_replay_recomputes_the_step_without_a_sync_or_report():
    """``replay`` (the replay rung's step) runs the same executable as
    ``step``: same output bits, no flag sync, and a fault reference left
    by the detected fault stays put for the rungs that certify against
    it."""
    state = _tree()
    can = ChecksumCanary(state, n_slices=1)
    fac = can.fuse_into_step(_raw_step, donate=False)
    want, _, rep = fac.step(0, state, BATCH)
    assert rep is None
    bad = dict(want, opt={"m": flip_bit(want["opt"]["m"], 11, 4)})
    _, _, rep = fac.step(1, bad, BATCH)
    assert rep is not None
    fault_ref = can._fault_reference
    dg.STATS.reset()
    got, aux = fac.replay(0, state, BATCH)
    assert dg.STATS.syncs == 0 and dg.STATS.launches == 1
    assert can._fault_reference is fault_ref
    assert _same_tree(got, want) and float(aux["loss"]) == 8.0


def test_degenerate_rotations_more_slices_than_leaves():
    """K > n_leaves: empty rotations run the plain step (no digest, no
    generation bump) and the populated rotations still guard their
    leaf."""
    tree = {"a": jnp.arange(8, dtype=jnp.int32),
            "b": jnp.ones((5,), jnp.float32)}
    K = 4
    can = ChecksumCanary(tree, n_slices=K)
    fac = can.fuse_into_step(_raw_step, donate=False)
    state = tree
    for s in range(2 * K):
        state, _, rep = fac.step(s, state, BATCH)
        assert rep is None
    # leaf "a" (plan index 0) is checked at steps ≡ 0 (mod K)
    bad = dict(state, a=flip_bit(state["a"], 2, 1))
    _, _, rep = fac.step(2 * K, bad, BATCH)
    assert rep is not None and rep.resolve() == ["a"]


def test_fuse_into_step_rejects_bad_warm_knob():
    can = ChecksumCanary(_tree(), n_slices=2)
    with pytest.raises(ValueError):
        can.fuse_into_step(_raw_step, warm="sometimes")
