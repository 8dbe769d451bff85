"""Mesh-sharded detection & recovery conformance (DESIGN.md §5).

Two tiers:

* **in-process mesh tests** — run when the process already has >= 8
  devices (the CI ``sharded`` job forces them with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; a plain
  1-device tier-1 run skips them):
    - shard digests bit-identical to the single-device uint32 oracle,
    - fault-flag all-reduce correctness + (leaf, shard) attribution,
    - partial-refresh contract on sharded generation tables,
    - shard-local recovery restores ONLY the injured shard,
    - donation + in-step-fused composition on the mesh,
    - campaign mesh regime reports the same outcomes as single-device.

* **a subprocess conformance smoke** — always runs (like the pipeline/MoE
  mesh tests): forces an 8-device CPU mesh in a child process and asserts
  the core contract (oracle bit-exactness, all-reduced flag, 1 launch +
  1 scalar sync per steady-state step), so the default tier-1 suite
  exercises the sharded path on every run.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

MESHABLE = len(jax.devices()) >= 8
mesh8 = pytest.mark.skipif(
    not MESHABLE,
    reason="needs >= 8 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _ctx():
    from repro.distributed.context import DistContext
    from repro.launch.mesh import make_mesh
    return DistContext.for_mesh(make_mesh((4, 2), ("data", "model")))


def _toy_tree(ctx):
    """Small tree covering the spec zoo: dim-0/dim-1/two-axis sharding,
    flat all-axis sharding, bf16, replicated matrix, replicated scalar."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(x, *spec):
        return jax.device_put(x, NamedSharding(ctx.mesh, P(*spec)))

    k = jax.random.PRNGKey
    return {
        "w_data": put(jax.random.normal(k(0), (16, 64)), "data", None),
        "w_model": put(jax.random.normal(k(1), (8, 32)), None, "model"),
        "w_both": put(jax.random.normal(k(2), (8, 16)), "data", "model"),
        "bf16": put(jax.random.normal(k(3), (64, 8)).astype(jnp.bfloat16),
                    ("data", "model"), None),
        "repl": put(jax.random.normal(k(4), (4, 4))),
        "counter": put(jnp.int32(3)),
    }


@mesh8
def test_shard_digests_bitexact_vs_single_device_oracle():
    from repro.kernels import digest as kd

    ctx = _ctx()
    tree = _toy_tree(ctx)
    plan = kd.sharded_plan_for(tree, ctx.mesh)
    assert plan.n_shards == 8
    table = np.asarray(plan.digest_table(tree))          # (8, L, 2)
    assert table.shape == (8, plan.n_leaves, 2)
    for i, key in enumerate(plan.keys):
        oracle = kd.host_shard_checksums(tree[key])
        assert np.array_equal(table[:, i], oracle), key
    # replicated leaves digest identically on every shard
    ri = plan.index_of("repl")
    assert all(np.array_equal(table[d, ri], table[0, ri]) for d in range(8))


@mesh8
def test_fault_flag_reduction_and_shard_attribution():
    from repro.core.detect import ChecksumCanary
    from repro.kernels import digest as kd

    ctx = _ctx()
    tree = _toy_tree(ctx)
    canary = ChecksumCanary(tree, n_slices=1, ctx=ctx)
    assert canary.check(0, tree) is None                 # clean: no fire

    # flip one element that lives on exactly one device's shard:
    # w_both (8, 16) P("data","model") -> local (2, 8); element [3, 9]
    # sits at data-row 1, model-col 1 => mesh position (1, 1) = shard 3
    bad = dict(tree)
    bad["w_both"] = tree["w_both"].at[3, 9].set(99.0)
    rep = canary.check(0, bad)
    assert rep is not None and rep.detector == "checksum"
    assert rep.leaves == ["w_both"]
    assert rep.shards == {"w_both": [3]}

    # a replicated leaf corrupts every shard's copy -> all shards named
    bad2 = dict(tree)
    bad2["repl"] = tree["repl"].at[1, 1].set(99.0)
    rep2 = canary.check(0, bad2)
    assert rep2 is not None and rep2.shards == {"repl": list(range(8))}

    # steady-state accounting: the check is 1 launch + 1 scalar sync
    kd.STATS.reset()
    assert canary.check(0, tree) is None
    assert kd.STATS.snapshot() == (1, 1, 0)


@mesh8
def test_partial_refresh_patches_without_generation_bump():
    """The refresh(keys=...) contract on SHARDED tables: named leaves'
    rows are patched in both generations (all shards), the generation is
    NOT bumped, and unrelated slices' references survive — the donated
    pair keeps passing mid-rotation."""
    from repro.core.detect import ChecksumCanary

    ctx = _ctx()
    tree = _toy_tree(ctx)
    canary = ChecksumCanary(tree, n_slices=3, ctx=ctx)

    state = tree
    for s in range(3):                                   # settle a rotation
        canary.arm_current(s, state)
        assert canary.check(s, state) is None

    gen = canary.generation
    # "repair" one leaf (new bytes) and partial-refresh just its rows
    state = dict(state)
    state["w_data"] = state["w_data"] * jnp.float32(1.5)
    canary.refresh(state, keys=["w_data"])
    assert canary.generation == gen, \
        "partial refresh must not bump the generation"

    # the repaired leaf certifies, and every UNRELATED slice's armed
    # reference is still valid through a full donated-pair rotation
    for s in range(3, 6):
        assert canary.check(s, state) is None, s
        canary.arm_current(s + 1, state)


@pytest.fixture(scope="module")
def mesh_train():
    """Shared sharded smoke train state + pinned step (compiled once)."""
    if not MESHABLE:
        pytest.skip("needs >= 8 devices")
    from repro.configs import get_config
    from repro.data.pipeline import TokenPipeline
    from repro.launch.specs import bind_state
    from repro.train.loop import make_train_state, make_train_step

    cfg = get_config("iterpro-100m").smoke()
    ctx = _ctx()
    B, S = 8, 32
    pipe = TokenPipeline(cfg.model.vocab_size, S, B, seed=0)
    state = make_train_state(cfg, jax.random.PRNGKey(0), global_batch=B)
    state, raw, bfn, sh = bind_state(
        ctx, cfg, state, make_train_step(cfg, global_batch=B),
        lambda s: pipe.batch_at(s))
    step = jax.jit(raw)
    st, m = step(state, bfn(0))
    jax.block_until_ready(m["loss"])
    return cfg, ctx, state, sh, raw, step, bfn


@mesh8
def test_shard_local_recovery_restores_only_injured_shard(mesh_train):
    from repro.core.detect import ChecksumCanary
    from repro.core.faults import InjectionPlan, inject
    from repro.core.icp import promote
    from repro.core.microcheckpoint import MicroCheckpointer
    from repro.core.recover import RecoveryRuntime
    from repro.core.recovery_table import RUNG_SHARD

    cfg, ctx, state0, sh, raw, step, bfn = mesh_train
    clone = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.array(x, copy=True), t)

    micro = MicroCheckpointer(interval=2, ctx=ctx)
    canary = ChecksumCanary(state0, n_slices=1, ctx=ctx)
    runtime = RecoveryRuntime(step_fn=step, batch_fn=bfn,
                              iv_registry=promote(cfg, 8), micro=micro,
                              shardings=sh)
    state = clone(state0)
    for s in range(4):
        micro.maybe_snapshot(s, state)
        ns, m = step(state, bfn(s))
        assert canary.check_and_arm(s, state, ns) is None
        state = ns
    micro.maybe_snapshot(4, state)                   # version-matched snap
    truth = jax.tree_util.tree_map(np.asarray, state)

    bad = inject(state, InjectionPlan("groups/0/0/ffn/up/w", 1000, 30, 0,
                                      "params"))
    # shard ids in FaultReport.shards are MESH-FLAT indices
    # (kernels.digest.mesh_device_order), not jax device ids — key the
    # pointer probes the same way
    from repro.kernels.digest import mesh_device_order
    flat = {dev: d for d, dev in enumerate(mesh_device_order(ctx.mesh))}
    leaf = bad["params"]["groups"][0][0]["ffn"]["up"]["w"]
    ptrs = {flat[sl.device]: sl.data.unsafe_buffer_pointer()
            for sl in leaf.addressable_shards}
    shard_bytes = leaf.addressable_shards[0].data.nbytes

    ns, m = step(bad, bfn(4))
    rep = canary.check_and_arm(4, bad, ns)
    assert rep is not None and rep.shards, rep
    injured = rep.shards["params/groups/0/0/ffn/up/w"]

    fixed, ev = runtime.recover(bad, rep, 4)
    assert ev.rung == RUNG_SHARD, ev
    # ONLY the injured shards' bytes moved host->device
    assert ev.bytes_moved == shard_bytes * len(injured), ev.bytes_moved
    healed = fixed["params"]["groups"][0][0]["ffn"]["up"]["w"]
    for sl in healed.addressable_shards:
        d = flat[sl.device]
        if d in injured:
            assert sl.data.unsafe_buffer_pointer() != ptrs[d]
        else:                      # healthy shards keep their exact buffer
            assert sl.data.unsafe_buffer_pointer() == ptrs[d]
    # and the patch is bit-exact against the pre-injection truth
    for a, b in zip(jax.tree_util.tree_leaves(fixed),
                    jax.tree_util.tree_leaves(truth)):
        assert np.array_equal(np.asarray(a), b)

    # version mismatch => the rung must abort into replay, never mix
    # state versions: advance one step past the snapshot, re-inject
    state = fixed
    ns, m = step(state, bfn(5))
    canary.refresh(state)
    bad = inject(state, InjectionPlan("groups/0/0/ffn/up/w", 1000, 30, 0,
                                      "params"))
    ns, m = step(bad, bfn(5))
    rep = canary.check_and_arm(5, bad, ns)
    assert rep is not None
    fixed2, ev2 = runtime.recover(bad, rep, 5)
    assert ev2.rung == "replay", ev2
    assert "shard_patch" in ev2.attempted, ev2


@mesh8
def test_donation_and_fused_detect_compose_on_mesh(mesh_train):
    """donate + fused-detect on the mesh: bit-identical trajectory to the
    plain sharded step, 1 combined launch + 1 scalar sync per step."""
    from repro.core.detect import ChecksumCanary
    from repro.kernels import digest as kd

    cfg, ctx, state0, sh, raw, step, bfn = mesh_train
    clone = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.array(x, copy=True), t)
    K = 2

    # truth: plain sharded steps
    truth = clone(state0)
    for s in range(2 * K):
        truth, _ = step(truth, bfn(s))
    truth = jax.tree_util.tree_map(np.asarray, truth)

    state = clone(state0)
    canary = ChecksumCanary(state, n_slices=K, ctx=ctx)
    factory = canary.fuse_into_step(raw, donate=True)
    for s in range(K):                                   # warm rotation
        state, m, rep = factory.step(s, state, bfn(s))
        assert rep is None
    kd.STATS.reset()
    for s in range(K, 2 * K):
        state, m, rep = factory.step(s, state, bfn(s))
        assert rep is None
    launches, syncs, traces = kd.STATS.snapshot()
    assert (launches, syncs, traces) == (K, K, 0)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(truth)):
        assert np.array_equal(np.asarray(a), b)


@mesh8
@pytest.mark.slow
def test_campaign_mesh_regime_outcome_conformance():
    """The seeded conformance campaign on the mesh must classify every
    constructed plan exactly like the single-device regimes (same
    outcome, same detector, recovered + exact), with recovery through
    either the shard_patch rung (version-matched snapshot: injections at
    even steps under interval=2) or replay."""
    import random

    from benchmarks._campaign import Campaign
    from repro.core import InjectionPlan
    from repro.core.recovery_table import RUNG_EQ1, RUNG_REPLAY, RUNG_SHARD

    campaign = Campaign(total_steps=8, snapshot_interval=2, seed=0,
                        ctx=_ctx())

    # expectations mirror tests/test_faults_campaign.py's single-device
    # CASES (same outcome + detector per regime); only the rung may
    # differ on the mesh: a version-matched snapshot (even-step
    # injection, interval 2, latency-0 checksum detection) upgrades the
    # full replay to the byte-minimal shard_patch.
    cases = [
        # (name, plan, canary (detector, rung), donated (detector, rung))
        ("norm-scale-b30",
         InjectionPlan("final_norm/scale", 3, 30, 2, "params"),
         ("nonfinite", RUNG_REPLAY),    # free trap fires before the canary
         ("checksum", RUNG_REPLAY)),    # pre-step check beats the traps
        ("ffn-b30-dormant",
         InjectionPlan("groups/0/0/ffn/up/w", 1000, 30, 3, "params"),
         ("checksum", RUNG_REPLAY),     # odd step: no version-matched snap
         ("checksum", RUNG_REPLAY)),
        ("wq-b27-benign",
         InjectionPlan("groups/0/0/attn/wq/w", 500, 27, 2, "params"),
         ("checksum", RUNG_SHARD),      # snapshot @2 == detection step 2
         ("checksum", RUNG_REPLAY)),
        ("iv-step-b12",
         InjectionPlan("step", 0, 12, 2, "iv"),
         ("checksum", RUNG_EQ1),        # IV block: Eq.(1) partner repair
         ("checksum", RUNG_REPLAY)),
    ]
    for name, plan, (det, rung), (ddet, drung) in cases:
        trial = campaign.run_trial(random.Random(0), plan=plan,
                                   use_canary=True, canary_slices=1)
        assert trial.outcome == "crash", (name, trial)
        assert trial.detector == det, (name, trial)
        assert trial.recovered and trial.exact, (name, trial)
        assert trial.rung == rung, (name, trial)
        assert 0 <= trial.latency_steps <= 1, (name, trial)

        donated = campaign.run_trial(random.Random(0), plan=plan,
                                     use_canary=True, canary_slices=1,
                                     donate=True)
        assert donated.outcome == "crash", (name, donated)
        assert donated.detector == ddet, (name, donated)
        assert donated.recovered and donated.exact, (name, donated)
        # donation kills the live buffers: unconditional replay pivot
        assert donated.rung == drung, (name, donated)


def test_single_axis_mesh_specs_degrade_to_pure_dp():
    """Regression: a pure data-parallel mesh ("--mesh 4" -> ("data",))
    has no "model" axis; every tensor-parallel spec rule must degrade to
    replication instead of raising KeyError.  Spec generation is
    allocation-free (ShapeDtypeStructs), so this runs on any device
    count."""
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.distributed.context import DistContext
    from repro.launch.specs import state_shardings, state_struct

    cfg = get_config("iterpro-100m").smoke()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    ctx = DistContext.for_mesh(mesh)
    assert ctx.tp_size == 1
    sh, specs = state_shardings(ctx, cfg, state_struct(cfg, 4))
    # no spec may name the absent axis
    for spec in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: hasattr(x, "index")):
        for entry in spec:
            names = (entry,) if isinstance(entry, str) else (entry or ())
            assert "model" not in names, spec


def test_recovery_table_sharded_ladders():
    """RecoveryTable.build(sharded=True) leads every non-IV ladder with
    the shard_patch rung; IV ladders keep Eq.(1) first (device-count
    independent — the table is pure metadata)."""
    from repro.core.recovery_table import (
        RUNG_EQ1,
        RUNG_SHARD,
        RecoveryTable,
    )

    state = {"params": {"w": np.zeros((4, 4), np.float32)},
             "iv": {"step": np.int32(0), "pos": np.int32(0)}}
    table = RecoveryTable.build(state, sharded=True)
    assert table.lookup("params/w").ladder[0] == RUNG_SHARD
    iv_entry = table.lookup("iv/step")
    assert RUNG_SHARD not in iv_entry.ladder
    assert iv_entry.ladder[0] == RUNG_EQ1
    # default build stays shard-free (single-device loops)
    assert RUNG_SHARD not in RecoveryTable.build(state).lookup(
        "params/w").ladder


# ---------------------------------------------------------------------------
# always-run subprocess smoke (the default tier-1 session has 1 device)
# ---------------------------------------------------------------------------

SHARDED_PROG = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.context import DistContext
    from repro.core.detect import ChecksumCanary
    from repro.kernels import digest as kd
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    ctx = DistContext.for_mesh(mesh)
    put = lambda x, *s: jax.device_put(x, NamedSharding(mesh, P(*s)))
    k = jax.random.PRNGKey
    tree = {
        "a": put(jax.random.normal(k(0), (16, 64)), "data", None),
        "b": put(jax.random.normal(k(1), (8, 32)), None, "model"),
        "c": put(jax.random.normal(k(2), (64,)).astype(jnp.bfloat16),
                 ("data", "model")),
        "s": put(jnp.int32(7)),
    }
    plan = kd.sharded_plan_for(tree, mesh)
    table = np.asarray(plan.digest_table(tree))
    oracle = all(np.array_equal(table[:, i],
                                kd.host_shard_checksums(tree[key]))
                 for i, key in enumerate(plan.keys))

    canary = ChecksumCanary(tree, n_slices=1, ctx=ctx)
    clean = canary.check(0, tree) is None
    kd.STATS.reset()
    canary.check(1, tree)
    acct = kd.STATS.snapshot()

    bad = dict(tree)
    bad["b"] = tree["b"].at[0, 20].set(99.0)   # model col 1 -> shards 1,3,5,7
    rep = canary.check(2, bad)
    print(json.dumps({
        "oracle": bool(oracle), "clean": bool(clean),
        "launches": acct[0], "syncs": acct[1], "traces": acct[2],
        "leaves": rep.leaves if rep else None,
        "shards": rep.shards if rep else None,
    }))
""")


def test_sharded_conformance_subprocess():
    """Core mesh contract on a forced 8-device child process: per-shard
    oracle bit-exactness, all-reduced flag, 1 launch + 1 scalar sync."""
    out = subprocess.run([sys.executable, "-c", SHARDED_PROG],
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["oracle"] is True
    assert data["clean"] is True
    assert (data["launches"], data["syncs"], data["traces"]) == (1, 1, 0)
    assert data["leaves"] == ["b"]
    assert data["shards"] == {"b": [1, 3, 5, 7]}
