"""Elastic hard-loss recovery — the chaos-drill suite (DESIGN.md §7).

Two tiers, mirroring test_sharded_resilience.py:

* **in-process mesh tests** (need >= 8 devices; the CI ``elastic`` job
  forces them): row-safe parity reconstruction into a DEGRADED target
  sharding with bit-identity against the pre-loss oracle (including the
  replica-dedup edge), the legacy-placement refusal, and the
  two-drills-in-one-process cache-eviction regression.

* **subprocess chaos drills** (always run): an 8-device child process
  trains, "loses" a device row mid-run (external ``FaultReport`` with
  ``lost_rows`` — the dead devices are never read again), recovers via
  the ``remesh`` rung with ZERO disk restores, and proves

    - the reconstructed state is bit-identical to the pre-loss oracle and
      digest-certified against the canary's surviving reference rows,
    - the post-resume loss trajectory is bit-identical to a clean
      degraded-mesh continuation from the oracle state (same global
      batch at reduced DP width),
    - the survivors' stolen loads reassemble the exact global batch,
    - the steady state after remesh keeps the 1-launch/1-sync/0-retrace
      contract (no hidden retraces against the dead mesh).
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
    """FSDP-flavoured spec zoo: data-dim-0, data-middle-dim (the layout
    that exposed the XLA SPMD concat miscompile), bf16 over (model, data),
    a data-sharded leaf REPLICATED over model (the dedup edge), and a
    fully replicated leaf (the re-gather path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(x, *spec):
        return jax.device_put(x, NamedSharding(ctx.mesh, P(*spec)))

    # data dims are divisible by 4 AND 3 so the same PartitionSpec
    # re-shards onto the degraded (3, 2) mesh
    k = jax.random.PRNGKey
    return {
        "w0": put(jax.random.normal(k(0), (12, 8)), "data", "model"),
        "w3d": put(jax.random.normal(k(1), (1, 60, 64)),
                   None, "data", "model"),
        "wbf": put(jax.random.normal(k(2), (4, 12)).astype(jnp.bfloat16),
                   "model", "data"),
        "wdup": put(jax.random.normal(k(3), (12, 6)), "data", None),
        "wrep": put(jax.random.normal(k(4), (8,))),
    }


def _host_oracle(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


@mesh8
class TestRowSafeReconstruction:
    def test_every_single_row_loss_reconstructs_bit_identical(self):
        """For EACH data row r: kill it, reconstruct every covered leaf
        from survivors + parity, re-gather the rest — bit-identical to
        the pre-loss oracle, reading nothing from the dead devices."""
        from repro.core.parity import ParityStore
        from repro.launch.elastic import _host_regather

        ctx = _ctx()
        tree = _toy_tree(ctx)
        oracle = _host_oracle(tree)
        ps = ParityStore(tree, ctx=ctx, row_safe=True)
        ps.build(tree)
        plan = ps.plan
        assert set(plan.keys) >= {"w0", "w3d", "wbf", "wdup"}
        assert "wrep" not in plan.key_set          # replicated: re-gather

        for row in range(4):
            dead = set(ctx.row_devices(row))
            pflat = plan.host_parity_flat(ps.parity, dead)
            for key, leaf in tree.items():
                if key in plan.key_set:
                    full, missing = plan.host_assemble_leaf(key, leaf, dead)
                    blocks = plan.host_surviving_blocks(key, leaf, dead)
                    uniq, _ = plan.slices[key]
                    for b in missing:
                        blk = plan.host_reconstruct_block(
                            key, b, pflat, blocks)
                        full[tuple(slice(a, e) for a, e in uniq[b])] = blk
                else:
                    full = _host_regather(leaf, dead)
                    assert full is not None
                got = np.atleast_1d(np.asarray(full))
                want = np.atleast_1d(oracle[key])
                assert got.dtype == want.dtype
                assert np.array_equal(got.view(np.uint8),
                                      want.view(np.uint8)), \
                    f"row {row}, leaf {key}: reconstruction not bit-exact"

    def test_reconstruct_into_degraded_target_sharding(self):
        """The reconstructed hosts re-shard onto the DEGRADED mesh's
        NamedShardings (the actual resume layout): values stay
        bit-identical and every committed shard lives on a survivor."""
        from jax.sharding import NamedSharding
        from repro.core.parity import ParityStore
        from repro.launch.elastic import _host_regather

        ctx = _ctx()
        tree = _toy_tree(ctx)
        oracle = _host_oracle(tree)
        ps = ParityStore(tree, ctx=ctx, row_safe=True)
        ps.build(tree)
        plan = ps.plan

        row = 3
        dead = set(ctx.row_devices(row))
        new_ctx = ctx.degrade((row,))
        assert new_ctx.mesh.shape["data"] == 3
        assert not (set(np.ravel(new_ctx.mesh.devices)) & dead)

        pflat = plan.host_parity_flat(ps.parity, dead)
        for key, leaf in tree.items():
            if key in plan.key_set:
                full, missing = plan.host_assemble_leaf(key, leaf, dead)
                blocks = plan.host_surviving_blocks(key, leaf, dead)
                uniq, _ = plan.slices[key]
                for b in missing:
                    full[tuple(slice(a, e) for a, e in uniq[b])] = \
                        plan.host_reconstruct_block(key, b, pflat, blocks)
            else:
                full = _host_regather(leaf, dead)
            # same PartitionSpec, shrunken mesh — the degraded layout
            spec = leaf.sharding.spec
            placed = jax.device_put(
                jnp.asarray(full),
                NamedSharding(new_ctx.mesh, spec))
            got = np.atleast_1d(np.asarray(placed))
            want = np.atleast_1d(oracle[key])
            assert np.array_equal(got.view(np.uint8), want.view(np.uint8))
            assert not ({sh.device for sh in placed.addressable_shards}
                        & dead)

    def test_replica_dedup_edge(self):
        """A data-sharded leaf replicated over 'model' holds TWO device
        copies per block: survivor reads must dedup (XOR-folding a block
        twice would self-cancel) and a row loss must still be a single
        erasure per fold group."""
        from repro.core.parity import ParityStore

        ctx = _ctx()
        tree = _toy_tree(ctx)
        ps = ParityStore(tree, ctx=ctx, row_safe=True)
        ps.build(tree)
        plan = ps.plan
        leaf = tree["wdup"]
        # 8 device shards but only 4 unique blocks
        uniq, dmap = plan.slices["wdup"]
        assert len(uniq) == 4 and len(dmap) == 8

        dead = set(ctx.row_devices(2))
        blocks = plan.host_surviving_blocks("wdup", leaf, dead)
        assert sorted(blocks) == [0, 1, 3]        # block 2 fully dead
        full, missing = plan.host_assemble_leaf("wdup", leaf, dead)
        assert missing == [2]
        pflat = plan.host_parity_flat(ps.parity, dead)
        blk = plan.host_reconstruct_block("wdup", 2, pflat, blocks)
        want = np.asarray(tree["wdup"])[uniq[2][0][0]:uniq[2][0][1]]
        assert np.array_equal(blk.view(np.uint8), want.view(np.uint8))

    def test_legacy_placement_refused_and_row_safe_required(self):
        """Default (legacy) parity placement puts parity row d on device
        d — a data-row loss takes parity down with the data.  The host
        read must refuse rather than hand back zeros, and on_loss must
        refuse to run on a legacy store."""
        from repro.core.parity import ParityStore
        from repro.launch.elastic import ElasticManager

        ctx = _ctx()
        tree = _toy_tree(ctx)
        legacy = ParityStore(tree, ctx=ctx)       # row_safe=False
        legacy.build(tree)
        dead = set(ctx.row_devices(1))
        with pytest.raises(RuntimeError, match="row_safe"):
            legacy.plan.host_parity_flat(legacy.parity, dead)

        emgr = ElasticManager(ctx)
        with pytest.raises(RuntimeError, match="row_safe"):
            emgr.on_loss(step=0, dead_rows=(1,), state=tree,
                         raw_step=lambda s, b: (s, {}), cfg=None,
                         batch_fn=lambda s: None, pstore=legacy)


@mesh8
def test_two_drills_in_one_process_evict_stale_mesh_caches():
    """(4,2) -> (3,2) -> (2,2): a second hard loss in the same process
    must run against the FIRST degraded mesh's executables/plans, so the
    drill asserts every global cache drops its old-mesh keys after each
    remesh, slice bookkeeping keeps ORIGINAL ids, and the final step
    still trains."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import parity as core_parity
    from repro.core.detect import ChecksumCanary
    from repro.core.parity import ParityStore
    from repro.data.pipeline import TokenPipeline
    from repro.kernels import digest as kdigest
    from repro.launch.elastic import ElasticManager
    from repro.launch.specs import bind_state
    from repro.train.loop import make_train_state, make_train_step

    def stale_keys(mesh):
        mk = kdigest._mesh_key(mesh)
        n = sum(1 for k in kdigest._SHARDED_PLAN_CACHE if k[0] == mk)
        n += sum(1 for k in core_parity._PARITY_PLAN_CACHE if k[0] == mk)
        return n

    cfg = get_config("iterpro-100m").smoke()
    cfg = dataclasses.replace(
        cfg, sharding=dataclasses.replace(cfg.sharding, fsdp=True))
    B, S = 12, 16
    ctx = _ctx()
    mesh0 = ctx.mesh
    pipe = TokenPipeline(cfg.model.vocab_size, S, B, seed=0)
    state = make_train_state(cfg, jax.random.PRNGKey(0), global_batch=B)
    raw_bfn = lambda s: pipe.batch_at(s)
    state, raw, bfn, sh = bind_state(
        ctx, cfg, state, make_train_step(cfg, global_batch=B), raw_bfn)
    step = jax.jit(raw)
    canary = ChecksumCanary(state, n_slices=1, ctx=ctx)
    pstore = ParityStore(state, ctx=ctx, row_safe=True)
    pstore.build(state)
    canary.attach_parity(pstore)
    assert stale_keys(mesh0) > 0                  # plans exist pre-drill

    new_state, m = step(state, bfn(0))
    assert canary.check_and_arm(0, state, new_state) is None
    state = new_state

    emgr = ElasticManager(ctx)
    r1 = emgr.on_loss(step=1, dead_rows=(3,), state=state, raw_step=raw,
                      cfg=cfg, batch_fn=raw_bfn, canary=canary,
                      pstore=pstore)
    assert r1.ctx.mesh.shape["data"] == 3
    assert r1.event.lost_slices == (3,)
    assert r1.event.uncertified_blocks == 0
    assert stale_keys(mesh0) == 0                 # old-mesh plans gone
    mesh1 = r1.ctx.mesh
    st1, m = r1.step(r1.state, r1.bfn(1))
    assert np.isfinite(float(m["loss"]))
    assert r1.canary.check_and_arm(1, r1.state, st1) is None

    # second drill: current row 2 is ORIGINAL slice 2
    r2 = emgr.on_loss(step=2, dead_rows=(2,), state=st1,
                      raw_step=r1.raw_step, cfg=cfg, batch_fn=raw_bfn,
                      canary=r1.canary, pstore=r1.pstore)
    assert r2.ctx.mesh.shape["data"] == 2
    assert r2.event.lost_slices == (2,)
    assert r2.event.uncertified_blocks == 0
    assert emgr.dead == {2, 3}
    assert emgr.slice_ids == [0, 1]
    assert stale_keys(mesh1) == 0
    st2, m = r2.step(r2.state, r2.bfn(2))
    assert np.isfinite(float(m["loss"]))
    # losing every surviving row is unrecoverable — must refuse loudly
    with pytest.raises(RuntimeError):
        emgr.on_loss(step=3, dead_rows=(0, 1), state=st2,
                     raw_step=r2.raw_step, cfg=cfg, batch_fn=raw_bfn,
                     canary=r2.canary, pstore=r2.pstore)


def test_bind_state_offmesh_passthrough(tiny_setup):
    """Off-mesh, bind_state is the identity recipe: no device_put, no
    pin, iterable unpack, pin() == identity."""
    from repro.launch.specs import bind_state

    cfg, state0, _, bfn = tiny_setup
    raw = lambda s, b: (s, {})
    bound = bind_state(None, cfg, state0, raw, bfn)
    st, step, bf, sh = bound
    assert st is state0 and step is raw and bf is bfn and sh is None
    assert bound.pin(raw) is raw


def test_kill_row_requires_elastic(tiny_cfg):
    from repro.launch.train import train

    with pytest.raises(ValueError, match="kill_row_at requires elastic"):
        train(tiny_cfg, steps=1, global_batch=2, seq_len=16,
              kill_row_at=0, verbose=False)


# ---------------------------------------------------------------------------
# subprocess chaos drills (always run: the child forces 8 CPU devices)
# ---------------------------------------------------------------------------

_DRILL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.detect import ChecksumCanary, FaultReport
    from repro.core.icp import promote
    from repro.core.microcheckpoint import MicroCheckpointer
    from repro.core.parity import ParityStore
    from repro.core.recover import RecoveryRuntime
    from repro.data.pipeline import TokenPipeline
    from repro.distributed.context import DistContext
    from repro.kernels import digest as kdigest
    from repro.launch.elastic import ElasticManager, stolen_batch
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import bind_state
    from repro.train.loop import make_train_state, make_train_step

    out = {}
    cfg = get_config("iterpro-100m").smoke()
    cfg = dataclasses.replace(
        cfg, sharding=dataclasses.replace(cfg.sharding, fsdp=True))
    B, S, KILL, STEPS = 12, 16, 3, 7
    ctx = DistContext.for_mesh(make_mesh((4, 2), ("data", "model")))
    pipe = TokenPipeline(cfg.model.vocab_size, S, B, seed=0)
    state = make_train_state(cfg, jax.random.PRNGKey(0), global_batch=B)
    raw_bfn = lambda s: pipe.batch_at(s)
    state, raw, bfn, sh = bind_state(
        ctx, cfg, state, make_train_step(cfg, global_batch=B), raw_bfn)
    step = jax.jit(raw)
    canary = ChecksumCanary(state, n_slices=1, ctx=ctx)
    pstore = ParityStore(state, ctx=ctx, row_safe=True)
    pstore.build(state)
    canary.attach_parity(pstore)
    out["parity_covers"] = len(pstore.plan.keys)
    emgr = ElasticManager(ctx)
    runtime = RecoveryRuntime(
        step_fn=step, batch_fn=bfn, iv_registry=promote(cfg, B),
        micro=MicroCheckpointer(interval=2, ctx=ctx), parity=pstore,
        shardings=sh, canary=canary,
        elastic=emgr.hook(raw_step=raw, cfg=cfg, batch_fn=raw_bfn,
                          canary=canary, pstore=pstore))

    losses = []
    for s in range(KILL):
        ns, m = step(state, bfn(s))
        assert canary.check_and_arm(s, state, ns) is None
        losses.append(float(m["loss"]))
        state = ns

    # pre-loss oracle (ground truth for the equivalence assertions; the
    # recovery path itself never reads the dead devices)
    oracle = jax.tree_util.tree_map(np.asarray, state)

    report = FaultReport(KILL, "external", lost_rows=(3,),
                         detail="chaos drill: row 3 lost")
    state, ev = runtime.recover(state, report, KILL)
    resume = runtime.pending_remesh
    out["rung"] = ev.rung
    out["attempted"] = list(ev.attempted)
    out["has_resume"] = resume is not None
    e = resume.event
    out["event"] = e.to_dict()
    out["new_dp"] = resume.ctx.mesh.shape["data"]

    # bit-identity of the reconstructed state vs the pre-loss oracle
    got = jax.tree_util.tree_map(np.asarray, resume.state)
    flat_g, _ = jax.tree_util.tree_flatten(got)
    flat_o, _ = jax.tree_util.tree_flatten(oracle)
    out["state_bit_identical"] = all(
        np.array_equal(np.atleast_1d(a).view(np.uint8),
                       np.atleast_1d(b).view(np.uint8))
        for a, b in zip(flat_g, flat_o))

    # no dead device holds any shard of the resumed state
    dead = set(ctx.row_devices(3))
    out["dead_unreferenced"] = not any(
        sh_.device in dead
        for leaf in jax.tree_util.tree_leaves(resume.state)
        for sh_ in leaf.addressable_shards)

    # survivors' stolen loads reassemble the exact global batch
    sb = stolen_batch(pipe, KILL, 4, (3,))
    ref = pipe.batch_at(KILL)
    out["stolen_batch_identity"] = all(
        np.array_equal(np.asarray(sb[k]), np.asarray(ref[k])) for k in ref)

    # drill continuation on the AOT-compiled resume step
    st = resume.state
    drill_losses = []
    for s in range(KILL, STEPS):
        ns, m = resume.step(st, resume.bfn(s))
        assert resume.canary.check_and_arm(s, st, ns) is None
        drill_losses.append(float(m["loss"]))
        st = ns

    # steady-state contract after remesh: 1 launch + 1 sync + 0 retraces
    kdigest.STATS.reset()
    extra = []
    for s in range(STEPS, STEPS + 2):
        ns, m = resume.step(st, resume.bfn(s))
        assert resume.canary.check_and_arm(s, st, ns) is None
        extra.append(float(m["loss"]))
        st = ns
    jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
    out["stats"] = kdigest.STATS.snapshot()

    # oracle continuation: a NEVER-FAILED run on the degraded mesh from
    # the pre-loss oracle state, same global batches — must match the
    # drill losses bit-exactly (deterministic CPU XLA)
    ob = bind_state(resume.ctx, cfg, oracle, raw, raw_bfn)
    ostep = jax.jit(ob.step)
    ost = ob.state
    oracle_losses = []
    for s in range(KILL, STEPS + 2):
        ost, m = ostep(ost, ob.bfn(s))
        oracle_losses.append(float(m["loss"]))
    out["losses_match_oracle"] = drill_losses + extra == oracle_losses
    out["drill_losses"] = drill_losses
    out["oracle_losses"] = oracle_losses
    print(json.dumps(out))
""")


def _run_child(prog, timeout=1200):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"),
         env.get("PYTHONPATH", "")])
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, f"child failed:\n{res.stdout}\n{res.stderr}"
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_chaos_drill_row_loss_resume():
    """THE drill: 8-device child, row 3 dies between steps, remesh rung
    recovers with zero disk restores, digest-certified bit-identical
    state, bit-identical degraded-trajectory losses, steady-state
    1/1/0 after resume."""
    out = _run_child(_DRILL)
    assert out["rung"] == "remesh"
    assert out["attempted"] == ["remesh"]         # no other rung touched
    assert out["has_resume"]
    assert out["new_dp"] == 3
    ev = out["event"]
    assert ev["disk_restores"] == 0               # zero disk-checkpoint
    assert ev["lost_slices"] == [3]
    assert ev["blocks_reconstructed"] > 0         # FSDP shards via parity
    assert ev["certified_blocks"] > 0             # vs surviving digests
    assert ev["uncertified_blocks"] == 0          # K=1: fully certified
    assert out["parity_covers"] > 0
    assert out["state_bit_identical"]
    assert out["dead_unreferenced"]
    assert out["stolen_batch_identity"]
    assert out["losses_match_oracle"], (
        out["drill_losses"], out["oracle_losses"])
    launches, syncs, traces = out["stats"]
    assert launches == 2 and syncs == 2 and traces == 0


def test_train_cli_elastic_kill_row_smoke():
    """The driver-level drill: --elastic --kill-row-at through the real
    train CLI, asserting the remesh event lands in the JSON report and
    the loop finishes every step at reduced DP width."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        import json
        from repro.configs import get_config
        from repro.launch.train import train

        cfg = get_config("iterpro-100m").smoke()
        out = train(cfg, steps=6, global_batch=8, seq_len=16,
                    canary_slices=1, mesh="4,2", parity=True,
                    elastic=True, kill_row_at=3, verbose=False)
        print(json.dumps(out))
    """)
    out = _run_child(prog)
    assert out["steps"] == 6
    assert out["faults_detected"] == 1 and out["faults_recovered"] == 1
    assert out["recovery"]["by_rung"] == {"remesh": 1}
    [ev] = out["elastic_events"]
    assert ev["lost_rows"] == [3] and ev["disk_restores"] == 0
    assert out["mesh"]["shape"] == {"data": 3, "model": 2}
