"""Integration tests for the recovery ladder: detection -> diagnosis ->
repair -> exact-or-abort verification, on a real (tiny) training loop."""

import dataclasses
import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (
    ChecksumCanary,
    FaultReport,
    InjectionPlan,
    MicroCheckpointer,
    ParityStore,
    RecoveryFailed,
    RecoveryRuntime,
    RecoveryTable,
    inject,
    promote,
    sample_plan,
)
from repro.core.induction import RecoveryAbort
from repro.core.recovery_table import (
    RUNG_EQ1,
    RUNG_OPT_IV,
    RUNG_REPLAY,
    RUNG_TRIAGE,
)
from repro.kernels import digest as dg


def _runtime(tiny_setup, **kw):
    cfg, state0, step, bfn = tiny_setup
    micro = MicroCheckpointer(interval=4)
    rt = RecoveryRuntime(step_fn=step, batch_fn=bfn,
                         iv_registry=promote(cfg, 2), micro=micro, **kw)
    return rt, micro


def _advance(step, bfn, state, start, n, micro=None):
    for s in range(start, start + n):
        if micro is not None:
            micro.maybe_snapshot(s, state)
            micro.record_iv(s, state["iv"])
        state, _ = step(state, bfn(s))
    return state


def test_iv_corruption_recovers_via_eq1(tiny_setup):
    cfg, state0, step, bfn = tiny_setup
    rt, micro = _runtime(tiny_setup)
    state = _advance(step, bfn, state0, 0, 6, micro)

    bad_iv = dict(state["iv"])
    bad_iv["sched_pos"] = jnp.int32(12345)
    bad = dict(state, iv=bad_iv)

    fixed, ev = rt.recover(bad, FaultReport(6, "checksum",
                                            leaves=["iv/sched_pos"]), 6)
    assert ev.rung == RUNG_EQ1
    assert int(fixed["iv"]["sched_pos"]) == int(state["iv"]["sched_pos"])


def test_param_corruption_replays_bit_exact(tiny_setup):
    cfg, state0, step, bfn = tiny_setup
    rt, micro = _runtime(tiny_setup)
    state = _advance(step, bfn, state0, 0, 6, micro)

    plan = sample_plan(random.Random(0), state, max_step=1, target="params")
    plan = dataclasses.replace(plan, bit=30)
    bad = inject(state, plan)

    fixed, ev = rt.recover(bad, FaultReport(6, "checksum",
                                            leaves=["params/" + plan.leaf]),
                           6)
    assert ev.rung == RUNG_REPLAY
    for a, b in zip(jax.tree_util.tree_leaves(fixed["params"]),
                    jax.tree_util.tree_leaves(state["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))  # BIT exact


def test_replay_runs_the_given_replay_step(tiny_setup):
    """With ``replay_step`` the replay rung recomputes each step through it
    (the hot path's own executable), never through ``step_fn``."""
    cfg, state0, step, bfn = tiny_setup
    ran = []

    def replay_step(s, st, batch):
        ran.append(s)
        return step(st, batch)

    rt, micro = _runtime(tiny_setup, replay_step=replay_step)
    state = _advance(step, bfn, state0, 0, 6, micro)
    plan = sample_plan(random.Random(1), state, max_step=1, target="params")
    fixed, ev = rt.recover(inject(state, plan),
                           FaultReport(6, "checksum",
                                       leaves=["params/" + plan.leaf]), 6)
    assert ev.rung == RUNG_REPLAY
    assert ran == [4, 5]
    for a, b in zip(jax.tree_util.tree_leaves(fixed),
                    jax.tree_util.tree_leaves(state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rot", [False, True], ids=["intact", "rotted"])
def test_replay_rung_is_exact_or_abort(tiny_setup, rot):
    """The replay rung uploads its snapshot, then digests it as uploaded:
    an untouched snapshot replays bit-exactly, and one flipped bit in the
    host copy aborts the rung naming that leaf.  Either way the attempt
    records one upload, then one verify."""
    cfg, state0, step, bfn = tiny_setup
    rt, micro = _runtime(tiny_setup)
    state = _advance(step, bfn, state0, 0, 6, micro)
    snap = micro.latest(before=6)
    key = next(k for k in snap.digests if k.startswith("params/"))

    def flip(path, leaf):
        if dg.leaf_key(path) != key:
            return leaf
        out = np.array(leaf)
        out.reshape(-1).view(np.uint8)[1] ^= 0x10
        return out

    if rot:
        snap.state = jax.tree_util.tree_map_with_path(flip, snap.state)
    with obs.span("probe.mark") as mark:
        pass
    report = FaultReport(6, "checksum", leaves=[key])
    if rot:
        with pytest.raises(RecoveryAbort, match=re.escape(key)):
            rt._rung_replay(state, report, 6)
    else:
        fixed, _ = rt._rung_replay(state, report, 6)
        for a, b in zip(jax.tree_util.tree_leaves(fixed),
                        jax.tree_util.tree_leaves(state)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    recs = [r for r in obs.records() if r.id > mark.id
            and r.name in ("snapshot.upload", "snapshot.verify")]
    assert [r.name for r in recs] == ["snapshot.upload", "snapshot.verify"]
    assert recs[0].end <= recs[1].start
    assert all(r.attrs["bytes"] > 0 for r in recs)


def test_post_recovery_trajectory_is_fault_free(tiny_setup):
    """The strongest claim: after recovery the continued trajectory equals
    the never-faulted trajectory bit-for-bit."""
    cfg, state0, step, bfn = tiny_setup
    rt, micro = _runtime(tiny_setup)

    # fault-free reference
    ref_state = _advance(step, bfn, state0, 0, 10)

    state = _advance(step, bfn, state0, 0, 6, micro)
    plan = dataclasses.replace(
        sample_plan(random.Random(1), state, max_step=1, target="params"),
        bit=27)
    bad = inject(state, plan)
    fixed, _ = rt.recover(bad, FaultReport(6, "checksum",
                                           leaves=["params/" + plan.leaf]), 6)
    final = _advance(step, bfn, fixed, 6, 4)

    for a, b in zip(jax.tree_util.tree_leaves(final),
                    jax.tree_util.tree_leaves(ref_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_replica_vote_rung(tiny_setup):
    cfg, state0, step, bfn = tiny_setup
    state = _advance(step, bfn, state0, 0, 3)
    replicas = lambda s: [state, state]          # two healthy DP partners
    rt, micro = _runtime(tiny_setup, replicas=replicas)

    plan = dataclasses.replace(
        sample_plan(random.Random(2), state, max_step=1, target="params"),
        bit=30)
    bad = inject(state, plan)
    fixed, ev = rt.recover(bad, FaultReport(3, "checksum",
                                            leaves=["params/" + plan.leaf]),
                           3, ladder=["replica_vote"])
    assert ev.rung == "replica_vote"
    for a, b in zip(jax.tree_util.tree_leaves(fixed["params"]),
                    jax.tree_util.tree_leaves(state["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_parity_rung_reconstructs_lost_shard(tiny_setup):
    cfg, state0, step, bfn = tiny_setup
    state = _advance(step, bfn, state0, 0, 2)
    ps = ParityStore(state)                 # covers the FULL state tree
    ps.build(state, 2)
    rt, micro = _runtime(tiny_setup, parity=ps)

    # wipe EXACTLY parity block 1 of one leaf (a lost device's slice):
    # the plan's own block boundaries define what "one shard" means
    key = "params/embed/table"
    table = state["params"]["embed"]["table"]
    csum = np.cumsum((0,) + ps.plan.block_sizes[key])
    lo, hi = int(csum[1]), min(int(csum[2]), table.size)
    flat = np.asarray(table).ravel().copy()
    flat[lo:hi] = np.nan
    bad_table = jnp.asarray(flat.reshape(table.shape))
    bad = dict(state, params=dict(state["params"],
                                  embed={"table": bad_table}))

    fixed, ev = rt.recover(bad, FaultReport(2, "external",
                                            leaves=[key]),
                           2, ladder=["parity_xor"])
    assert ev.rung == "parity_xor"
    assert ev.steps_replayed == 0
    assert ev.bytes_moved > 0
    assert np.array_equal(np.asarray(fixed["params"]["embed"]["table"]),
                          np.asarray(table))


def test_exhausted_ladder_raises(tiny_setup):
    cfg, state0, step, bfn = tiny_setup
    rt, micro = _runtime(tiny_setup)      # no snapshots taken, no checkpoint
    state = _advance(step, bfn, state0, 0, 2)
    bad_iv = {k: jnp.int32(int(v) + 7 + i)       # break ALL counters
              for i, (k, v) in enumerate(state["iv"].items())}
    bad = dict(state, iv=bad_iv)
    with pytest.raises(RecoveryFailed):
        rt.recover(bad, FaultReport(2, "checksum",
                                    leaves=[f"iv/{k}" for k in bad_iv]), 2)


def test_canary_detects_and_names_leaf(tiny_setup):
    cfg, state0, step, bfn = tiny_setup
    canary = ChecksumCanary(state0, n_slices=1)   # check everything
    plan = dataclasses.replace(
        sample_plan(random.Random(3), state0, max_step=1, target="params"),
        bit=5)   # low mantissa bit: invisible to loss traps
    bad = inject(state0, plan)
    report = canary.check(0, bad)
    assert report is not None
    assert report.leaves == ["params/" + plan.leaf]


def test_recovery_table_roundtrip(tiny_setup):
    cfg, state0, step, bfn = tiny_setup
    table = RecoveryTable.build(state0, replicated=True, parity=True)
    assert len(table) == len(jax.tree_util.tree_leaves(state0))
    again = RecoveryTable.from_json(table.to_json())
    assert again.entries == table.entries
    e = again.lookup("iv/step")
    assert e is not None and e.ladder[0] == RUNG_EQ1


def test_every_emittable_rung_has_a_registered_handler(tiny_setup):
    """Dead-rung sweep: every rung name RecoveryTable.build can emit —
    under ANY combination of redundancy flags — must resolve to a handler
    in RecoveryRuntime._RUNGS, or recover() would skip it silently (the
    ladder driver ignores unknown rungs)."""
    cfg, state0, step, bfn = tiny_setup
    reg = promote(cfg, 2)
    opt_ivs = tuple(sorted(k for k in (set(reg.specs) | set(reg.derived))
                           if not k.startswith("iv/")))
    assert opt_ivs, "promote() must export optimizer-owned induction keys"
    emittable = set()
    for replicated in (False, True):
        for parity in (False, True):
            for sharded in (False, True):
                for triage in (False, True):
                    for elastic in (False, True):
                        table = RecoveryTable.build(
                            state0, replicated=replicated, parity=parity,
                            sharded=sharded, triage=triage,
                            elastic=elastic, opt_ivs=opt_ivs)
                        for entry in table.entries.values():
                            emittable.update(entry.ladder)
    missing = emittable - set(RecoveryRuntime._RUNGS)
    assert not missing, f"rungs with no registered handler: {missing}"
    # ...and no handler is dead weight: the flag space above reaches all
    # (triage and opt_iv included — a handler the table can never emit
    # would be untestable dead code)
    assert emittable == set(RecoveryRuntime._RUNGS)


def test_eq1_residue_abort_regression():
    """data_offset advances by the global batch (a non-unit step): a
    partner value off that lattice is itself corrupted, and Eq.(1) must
    refuse it instead of floor-dividing into a silently wrong repair."""
    from repro.core.induction import IVRegistry, RecoveryAbort

    reg = IVRegistry({"iv/step": (0, 1), "iv/data_offset": (0, 512)})
    assert reg.eq1("iv/step", "iv/data_offset", 512 * 7) == 7
    with pytest.raises(RecoveryAbort):
        reg.eq1("iv/step", "iv/data_offset", 512 * 7 + 3)


def test_opt_counter_flip_recovers_via_opt_iv(tiny_setup):
    """A bit flip in the optimizer's own step counter repairs through the
    opt_iv branch of the Eq.(1) consensus engine: zero snapshot bytes,
    zero replayed steps."""
    cfg, state0, step, bfn = tiny_setup
    rt, micro = _runtime(tiny_setup)
    state = _advance(step, bfn, state0, 0, 6, micro)

    bad = inject(state, InjectionPlan("t", 0, 3, 6, "opt"))
    assert int(bad["opt"]["t"]) != int(state["opt"]["t"])
    fixed, ev = rt.recover(bad, FaultReport(6, "checksum",
                                            leaves=["opt/t"]), 6)
    assert ev.rung == RUNG_OPT_IV
    assert ev.steps_replayed == 0
    assert ev.bytes_moved == 0
    assert int(fixed["opt"]["t"]) == int(state["opt"]["t"])


def test_derived_correction_flip_recomputed_bitwise(tiny_setup):
    """Bias-correction scalars are DERIVED induction entries: a flip in
    one is repaired by recomputing it from the consensus iteration, and
    the recomputation must be bit-identical to the never-faulted value."""
    cfg, state0, step, bfn = tiny_setup
    rt, micro = _runtime(tiny_setup)
    state = _advance(step, bfn, state0, 0, 6, micro)

    bad = inject(state, InjectionPlan("bc1", 0, 20, 6, "opt"))
    fixed, ev = rt.recover(bad, FaultReport(6, "checksum",
                                            leaves=["opt/bc1"]), 6)
    assert ev.rung == RUNG_OPT_IV
    assert ev.steps_replayed == 0
    assert (np.asarray(fixed["opt"]["bc1"]).tobytes()
            == np.asarray(state["opt"]["bc1"]).tobytes())   # BIT exact
    # the healthy twin was untouched by the repair
    assert (np.asarray(fixed["opt"]["bc2"]).tobytes()
            == np.asarray(state["opt"]["bc2"]).tobytes())


def test_triage_tolerates_sub_epsilon_moment_flip(tiny_setup):
    """Rung 0: a mantissa-tail flip in an EMA moment carries a certified
    below-epsilon perturbation — triage tolerates it in place (state
    untouched) and re-arms the digest row so the canary stays quiet."""
    cfg, state0, step, bfn = tiny_setup
    state = _advance(step, bfn, state0, 0, 6)
    canary = ChecksumCanary(state, n_slices=1)
    rt, micro = _runtime(tiny_setup, canary=canary, triage=True)

    plan = InjectionPlan("m/groups/0/0/ffn/up/w", 1000, 1, 6, "opt")
    bad = inject(state, plan)
    report = canary.check(6, bad)
    assert report is not None and report.detector == "checksum"
    assert report.leaves == ["opt/" + plan.leaf]

    fixed, ev = rt.recover(bad, report, 6)
    assert ev.rung == RUNG_TRIAGE
    assert ev.steps_replayed == 0
    assert ev.bytes_moved == 0
    # tolerate never alters state — the flipped bit is still there
    for a, b in zip(jax.tree_util.tree_leaves(fixed),
                    jax.tree_util.tree_leaves(bad)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # ...and the digest table was re-armed to the tolerated bits, so the
    # very next check does NOT re-fire on the value we chose to live with
    assert canary.check(7, fixed) is None


def test_triage_escalates_uncertifiable_flip(tiny_setup):
    """An exponent-scale flip in the same moment leaf fails the epsilon
    certificate: triage must abort into the rest of the ladder (replay
    here), preserving exact-or-abort."""
    cfg, state0, step, bfn = tiny_setup
    rt, micro = _runtime(tiny_setup, triage=True)
    state = _advance(step, bfn, state0, 0, 6, micro)
    canary = ChecksumCanary(state, n_slices=1)
    rt.canary = canary

    plan = InjectionPlan("m/groups/0/0/ffn/up/w", 1000, 30, 6, "opt")
    bad = inject(state, plan)
    report = canary.check(6, bad)
    assert report is not None

    fixed, ev = rt.recover(bad, report, 6)
    assert ev.rung == RUNG_REPLAY            # escalated past rung 0
    assert "escalate" in ev.report.detail
    for a, b in zip(jax.tree_util.tree_leaves(fixed),
                    jax.tree_util.tree_leaves(state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))  # BIT exact


def test_triage_tolerates_int8_pad_tail_flip(tiny_setup):
    """Dead-region certificate: a flip in the int8-quantised moment pad
    tail (bytes _dq8 never reads, rewritten wholesale each update) is
    tolerated bitwise — no epsilon needed."""
    from repro.optim.optimizers import _q8

    p = jnp.arange(300, dtype=jnp.float32) / 7.0    # pads to 2x256 blocks
    state = {"params": {"w": p}, "opt": {"m": {"w": _q8(p)}},
             "iv": {"step": jnp.int32(4)}}
    canary = ChecksumCanary(state, n_slices=1)
    rt, micro = _runtime(tiny_setup, canary=canary, triage=True)

    bad = inject(state, InjectionPlan("m/w/q", 310, 6, 4, "opt"))
    report = canary.check(4, bad)
    assert report is not None and report.leaves == ["opt/m/w/q"]

    fixed, ev = rt.recover(bad, report, 4)
    assert ev.rung == RUNG_TRIAGE
    assert "dead-region" in ev.report.detail
    assert canary.check(5, fixed) is None    # re-armed


def test_triage_dead_element_boundary(tiny_setup):
    """The dead-element predicate draws the line exactly at the logical
    param size: pad-tail elements certify, live elements never do."""
    from repro.optim.optimizers import QBLOCK, _q8

    rt, micro = _runtime(tiny_setup)
    p = jnp.arange(300, dtype=jnp.float32)
    state = {"params": {"w": p}, "opt": {"m": {"w": _q8(p)}},
             "iv": {"step": jnp.int32(0)}}
    assert rt._dead_element(state, "opt/m/w/q", 300)       # first pad elt
    assert rt._dead_element(state, "opt/m/w/q", 511)       # last pad elt
    assert not rt._dead_element(state, "opt/m/w/q", 299)   # last live elt
    # both scale rows cover live elements (block 1 holds 256..299)
    assert not rt._dead_element(state, "opt/m/w/scale", 0)
    assert not rt._dead_element(state, "opt/m/w/scale", 1)
    assert rt._dead_element(state, "opt/m/w/scale", 2)     # all-pad block
    # never certifies outside the quantised-moment subtree
    assert not rt._dead_element(state, "params/w", 500)


def test_replica_vote_routes_through_vote_kernel():
    """The TMR rung's repair math IS kernels/vote.py: kops.vote3 (the op
    _rung_replica calls) must produce vote3_tiles' bitwise majority."""
    from repro.kernels import ops as kops
    from repro.kernels import vote as kvote

    rng = np.random.default_rng(0)
    a = rng.standard_normal((300, 7)).astype(np.float32)
    b = a.copy()
    c = a.copy()
    bad = a.copy()
    bad[13, 2] = np.float32(1e30)          # any single-copy corruption
    fixed = np.asarray(kops.vote3(jnp.asarray(bad), jnp.asarray(b),
                                  jnp.asarray(c)))
    assert np.array_equal(fixed, a)
    # and the op is literally the Pallas kernel, not a reimplementation
    import inspect
    assert "vote3_tiles" in inspect.getsource(kops.vote3)
    assert kvote.vote3_tiles is not None
