"""Fig 9: no-fault runtime overhead of the resilience subsystem.

Paper claim: ~0% runtime overhead + 27 MB fixed memory, because detection
is free (SIGSEGV) and the runtime is off the hot path.

Here: free traps read scalars the step already computed (literally free);
the only paid component is the optional rotating canary — one fused digest
launch + one scalar device→host sync per step regardless of leaf count
(DESIGN.md §4.2).  We measure steps/s for: no detectors / traps only /
traps + canary at K in {8, 4, 1}, plus the micro-checkpoint memory cost,
plus a detection-throughput microbenchmark (GB/s digested, launches/step,
syncs/step) comparing the fused engine against the seed's per-leaf path.
In a multi-device process the sharded section additionally HARD-ASSERTS
the DESIGN.md §5 mesh cost model: 1 launch + 1 all-reduced scalar sync
per step, per-shard oracle bit-exactness, and the /D per-device byte
split."""

from __future__ import annotations

import time
from collections import deque
from typing import Dict, Optional

import jax
import numpy as np

from benchmarks._campaign import Campaign
from repro.core import ChecksumCanary, MicroCheckpointer, trap_loss_spike, trap_nonfinite
from repro.core.detect import LOSS_WINDOW
from repro.kernels import digest as kdigest
from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh


def _loop(campaign: Campaign, steps: int, *, traps: bool, canary_k: int,
          snapshots: bool, donate: bool = False,
          fused: bool = False) -> float:
    """Returns steps/sec over `steps` warm steps."""
    state = campaign.states[0]
    if donate:
        # a donated loop consumes its input buffers — run on a private
        # deep copy so the campaign's ground-truth states survive
        state = campaign.clone(state)
        step_fn = campaign.donated_step()
    else:
        step_fn = campaign.step
    canary = ChecksumCanary(state, n_slices=canary_k) if canary_k else None
    factory = canary.fuse_into_step(campaign.raw_step(), donate=donate) \
        if fused and canary is not None else None
    micro = MicroCheckpointer(interval=2) if snapshots else None
    history = deque(maxlen=LOSS_WINDOW)   # bounded: the trap only ever
    # reads the last LOSS_WINDOW values
    # warm the step and one full canary rotation (compiles the K fused
    # step functions once; steady-state per-step cost is what we measure)
    s0 = 0
    if factory is not None:
        # AOT-compile all K rotation executables, then settle one full
        # rotation THROUGH the factory so every executable has run once
        # before the timer starts (matching the execution-warmed unfused
        # rows); stepping via the factory keeps the canary table and the
        # state version in lockstep, so the timed loop resumes at s=K
        factory.warm(state, campaign.bfn(0))
        for s in range(canary.n_slices):
            state, m, _ = factory.step(s, state, campaign.bfn(s))
        jax.block_until_ready(m["loss"])
        s0 = canary.n_slices
    elif donate:
        state, m = step_fn(state, campaign.bfn(0))
        jax.block_until_ready(m["loss"])
    else:
        st, m = step_fn(state, campaign.bfn(0))
        jax.block_until_ready(m["loss"])
    if canary is not None and factory is None:
        for s in range(canary.n_slices):
            if donate:
                canary.arm_current(s, state)
                canary.check(s, state)
            else:
                canary.check_and_arm(s, state)
    t0 = time.perf_counter()
    for s in range(s0, s0 + steps):
        if canary is not None and donate and factory is None:
            # donated pair, arm half: digest slice s%K of the buffer the
            # previous step produced (one launch, no sync)
            canary.arm_current(s, state)
        if micro is not None:
            micro.maybe_snapshot(s, state)
            micro.record_iv(s, state["iv"])
        if canary is not None and donate and factory is None:
            # check half: verify the same slice of the same version at the
            # buffer's last readable moment (one launch + one scalar sync)
            canary.check(s, state)
        if factory is not None:
            # in-step fused: detection rides the step's own launch — ONE
            # combined launch + ONE scalar sync per step
            new_state, metrics, _ = factory.step(s, state, campaign.bfn(s))
        else:
            new_state, metrics = step_fn(state, campaign.bfn(s))
        if traps:
            trap_nonfinite(s, metrics) or \
                trap_loss_spike(s, metrics, history)
            history.append(float(metrics["loss"]))
        if canary is not None and not donate and factory is None:
            # one fused launch + one scalar sync: check slice s%K of the
            # pre-step state, arm slice (s+1)%K of the fresh output
            canary.check_and_arm(s, state, new_state)
        state = new_state
    jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
    return steps / (time.perf_counter() - t0)


def _per_leaf_checksums(tree) -> Dict[str, np.ndarray]:
    """The SEED detection path, kept as the benchmark baseline: one jit'd
    ``checksum`` dispatch + one blocking device→host transfer per leaf."""
    out = {}

    def visit(path, leaf):
        out[kops.leaf_key(path)] = np.asarray(kops.checksum(leaf))
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return out


def digest_throughput(campaign: Campaign, reps: int = 10) -> Dict:
    """Detection-cost microbenchmark: whole-state digest via the fused
    single-launch engine vs the seed per-leaf path, on the same state."""
    state = campaign.states[0]
    plan = kdigest.plan_for(state)
    state_bytes = sum(np.dtype(jax.numpy.result_type(x)).itemsize *
                      int(np.prod(jax.numpy.shape(x)) or 1)
                      for x in jax.tree_util.tree_leaves(state))

    # fused (one launch, digest table stays on device, zero syncs) vs the
    # seed path (O(leaves) launches + blocking transfers) — interleaved
    # and median-reduced so a noisy-neighbour scheduler can't flip the
    # comparison
    jax.block_until_ready(plan.digest_table(state))          # warm/compile
    _per_leaf_checksums(state)                               # warm/compile
    fused_t, per_leaf_t = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(plan.digest_table(state))
        fused_t.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _per_leaf_checksums(state)
        per_leaf_t.append(time.perf_counter() - t0)
    fused_s = float(np.median(fused_t))
    per_leaf_s = float(np.median(per_leaf_t))

    # hot-path accounting for one steady-state canary check+arm: warm a
    # FULL rotation first (each of the K rotations compiles its own fused
    # step function exactly once)
    canary = ChecksumCanary(state, n_slices=8)
    for s in range(canary.n_slices):                         # warm/compile
        canary.check_and_arm(s, state)
    kdigest.STATS.reset()
    canary.check_and_arm(canary.n_slices, state)
    launches, syncs, traces = kdigest.STATS.snapshot()

    return {
        "n_leaves": plan.n_leaves,
        "state_mb": state_bytes / 1e6,
        "digested_mb_per_pass": plan.bytes_per_pass / 1e6,
        "fused_ms": 1e3 * fused_s,
        "per_leaf_ms": 1e3 * per_leaf_s,
        "fused_gbps": plan.bytes_per_pass / fused_s / 1e9,
        "per_leaf_gbps": plan.bytes_per_pass / per_leaf_s / 1e9,
        "speedup": per_leaf_s / fused_s,
        "canary_launches_per_step": launches,
        "canary_syncs_per_step": syncs,
        "canary_retraces_per_step": traces,
    }


def donation_steady_state(campaign: Campaign, steps: int = 16) -> Dict:
    """Donation-mode hot-path accounting (the PR-3 tentpole contract):

    * the digest path makes ZERO new device allocations per steady-state
      step — the persistent packing buffer is donated through every
      launch (``input_output_aliases`` on the pack kernel) and the
      write-generation reference table is scatter-armed in place;
    * the packing buffers are POINTER-STABLE: the same HBM ranges are
      rewritten every step;
    * per donated step the canary pair costs 2 launches (arm: no sync,
      check: ONE scalar sync), 0 retraces — same 2/K bytes as the fused
      non-donated call.
    """
    import gc

    state = campaign.clone(campaign.states[0])
    step_fn = campaign.donated_step()
    canary = ChecksumCanary(state, n_slices=8)
    state, m = step_fn(state, campaign.bfn(0))
    jax.block_until_ready(m["loss"])
    # warm every rotation's arm/check pair (compiles once per rotation)
    for s in range(canary.n_slices):
        canary.arm_current(s, state)
        canary.check(s, state)
    # record the packing-buffer addresses, then settle one full rotation:
    # probing unsafe_buffer_pointer leaves per-buffer residue that the
    # next donation of each subset flushes, and the live-array window
    # below must contain only steady-state work
    subsets = list(canary.plan._pack_bufs.keys())
    union_ptrs = {idx: canary.plan.buffer_pointer(idx) for idx in subsets}
    for s in range(canary.n_slices):
        canary.arm_current(s, state)
        canary.check(s, state)
        new_state, metrics = step_fn(state, campaign.bfn(s))
        state = new_state
    jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])

    gc.collect()
    live0 = len(jax.live_arrays())
    kdigest.STATS.reset()
    for s in range(steps):
        canary.arm_current(s, state)
        canary.check(s, state)
        new_state, metrics = step_fn(state, campaign.bfn(s))
        state = new_state
    jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
    gc.collect()
    live1 = len(jax.live_arrays())
    launches, syncs, traces = kdigest.STATS.snapshot()
    ptr_stable = all(canary.plan.buffer_pointer(idx) == p
                     for idx, p in union_ptrs.items())

    # donation-effectiveness probe: a digest must CONSUME the buffer it
    # was handed (the donated object dies) and hand back the same HBM
    # range.  A silently vetoed donation (e.g. a stray host view pinning
    # the buffer) would leave the old object alive and/or move the
    # address — the live-array delta alone cannot see that, since a
    # fresh-alloc-and-free per step also nets zero.  Probe rotation 0's
    # registered (hot-path-persistent) slice buffer with one more pair.
    plan = canary.plan
    idx_probe = tuple(canary._slice_indices(0))
    probe_buf = plan._pack_bufs[idx_probe]
    probe_ptr = plan.buffer_pointer(idx_probe)
    canary.arm_current(0, state)
    canary.check(0, state)
    donation_effective = bool(probe_buf.is_deleted()
                              and plan.buffer_pointer(idx_probe) == probe_ptr)

    # digest-only throughput of the donated pair (no step compute in the
    # timed window): bytes = 2 rotating slices of the packed state per step
    t0 = time.perf_counter()
    for s in range(steps):
        canary.arm_current(s + 1, state)
        canary.check(s + 1, state)
    digest_wall = time.perf_counter() - t0
    digested_bytes = 2 * canary.plan.bytes_per_pass / canary.n_slices
    return {
        "steps": steps,
        # net live-array growth (leak detector); 0 allocs/step is proven
        # by donation_effective + pack_buffer_ptr_stable, not this alone
        "net_new_live_arrays_per_step": (live1 - live0) / steps,
        "pack_buffer_ptr_stable": ptr_stable,
        "donation_effective": donation_effective,
        "canary_launches_per_step": launches / steps,
        "canary_syncs_per_step": syncs / steps,
        "canary_retraces_per_step": traces / steps,
        "digested_mb_per_step": digested_bytes / 1e6,
        "digest_gbps": digested_bytes * steps / digest_wall / 1e9,
    }


def fused_steady_state(campaign: Campaign, steps: int = 16,
                       n_slices: int = 8) -> Dict:
    """In-step fused detection accounting (the PR-4 tentpole contract;
    DESIGN.md §4.2 "in-step fused" column):

    * steady state (after the K-executable warmup) is EXACTLY 1 combined
      launch + 1 scalar device→host sync per step — detection adds zero
      dispatches to the donated step;
    * warmup = K rotation-specialised AOT compilations (wall time and
      count reported: the price of fusing detection into the step);
    * zero retraces in steady state (the executable cache holds);
    * digests bit-exact to the per-leaf oracle: the slice armed by a
      steady-state fused step matches ``ref.checksum_ref`` of the same
      output bytes (probed via a device-temp host copy so the probe
      cannot veto donation).
    """
    from repro.kernels import ref as kref

    state = campaign.clone(campaign.states[0])
    canary = ChecksumCanary(state, n_slices=n_slices)
    factory = canary.fuse_into_step(campaign.raw_step(), donate=True)
    warm_s = factory.warm(state, campaign.bfn(0))

    # settle one full rotation so every executable has run once
    for s in range(n_slices):
        state, m, rep = factory.step(s, state, campaign.bfn(s))
        assert rep is None
    jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])

    kdigest.STATS.reset()
    t0 = time.perf_counter()
    for s in range(n_slices, n_slices + steps):
        state, m, rep = factory.step(s, state, campaign.bfn(s))
        assert rep is None
    jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
    wall = time.perf_counter() - t0
    launches, syncs, traces = kdigest.STATS.snapshot()

    # oracle probe: one more fused step; the freshly armed rows (read
    # generation after the commit) must equal the per-leaf oracle digests
    # of the output state's arm slice
    s = n_slices + steps
    new_state, m, rep = factory.step(s, state, campaign.bfn(s))
    arm_idx = canary._slice_indices(s + 1)
    out_leaves = canary.plan.leaves(new_state)
    table = np.asarray(jax.numpy.array(canary.reference, copy=True))
    oracle_exact = all(
        np.array_equal(table[i],
                       np.asarray(kref.checksum_ref(
                           jax.numpy.array(out_leaves[i], copy=True))))
        for i in arm_idx)

    digested_bytes = 2 * canary.plan.bytes_per_pass / n_slices
    return {
        "steps": steps,
        "n_slices": n_slices,
        "warmup_compiles": factory.n_compiles,
        "warmup_compile_s": factory.compile_seconds,
        "warmup_wall_s": warm_s,
        "launches_per_step": launches / steps,
        "syncs_per_step": syncs / steps,
        "retraces_per_step": traces / steps,
        "digested_mb_per_step": digested_bytes / 1e6,
        "steps_per_s": steps / wall,
        "oracle_exact": bool(oracle_exact),
    }


def sharded_steady_state(campaign: Campaign, steps: int = 10,
                         n_slices: int = 8) -> Optional[Dict]:
    """Mesh-sharded detection accounting (the DESIGN.md §5 cost model;
    requires >1 device — on CPU force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``):

    * sharded steady-state detection is EXACTLY 1 combined launch + 1
      scalar host sync per step — in fused ``check_and_arm`` form AND in
      in-step fused (donated) form — with 0 retraces: the mesh adds no
      dispatches and no extra host traffic; the one fetched scalar is the
      all-reduced fault flag, the only cross-device communication on the
      no-fault path (all asserted, not just reported);
    * the donated pair keeps its 2-launch/1-sync contract;
    * shard digests are bit-identical to the single-device uint32 oracle
      (``host_shard_checksums`` of every leaf's shard bytes — asserted);
    * byte accounting matches the model: the global pass digests the
      whole packed state (bytes_per_pass == n_shards × local pass), each
      step streams ~2B/K of it, and every device streams exactly 1/D of
      that.
    """
    n_dev = len(jax.devices())
    if n_dev < 2:
        return None
    from repro.distributed.context import DistContext

    if campaign.ctx is not None:
        # mesh-regime campaign: its step is already pinned to its own
        # mesh/shardings — reuse them (pinning again onto a second mesh
        # would reshard every leaf every step and corrupt the very
        # accounting this section asserts)
        ctx = campaign.ctx
        mesh = ctx.mesh
        state = campaign.clone(campaign.states[0])
        bfn = campaign.bfn
        raw = campaign.raw_step()
    else:
        if n_dev >= 4 and n_dev % 2 == 0:
            mesh = make_mesh((n_dev // 2, 2), ("data", "model"))
        else:
            mesh = make_mesh((n_dev,), ("data",))
        ctx = DistContext.for_mesh(mesh)
        from repro.launch.specs import bind_state
        state, raw, bfn, _ = bind_state(
            ctx, campaign.cfg, campaign.clone(campaign.states[0]),
            campaign.raw_step(), campaign.bfn)
    step_fn = jax.jit(raw)

    canary = ChecksumCanary(state, n_slices=n_slices, ctx=ctx)
    plan = canary.plan
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))

    # oracle: every (leaf, shard) digest must equal the single-device
    # uint32 oracle of exactly that shard's bytes
    leaves = plan.leaves(state)
    table = np.asarray(jax.numpy.array(plan.digest_table(state), copy=True))
    oracle_exact = all(
        np.array_equal(table[:, i], kdigest.host_shard_checksums(leaves[i]))
        for i in range(plan.n_leaves))
    assert oracle_exact, "sharded digests diverge from the per-shard oracle"

    # --- fused check_and_arm: 1 launch + 1 scalar sync per step ---------
    st = state
    for s in range(n_slices):                                # warm/compile
        ns, m = step_fn(st, bfn(s))
        assert canary.check_and_arm(s, st, ns) is None
        st = ns
    jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
    kdigest.STATS.reset()
    t0 = time.perf_counter()
    for s in range(n_slices, n_slices + steps):
        ns, m = step_fn(st, bfn(s))
        assert canary.check_and_arm(s, st, ns) is None
        st = ns
    jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
    wall = time.perf_counter() - t0
    launches, syncs, traces = kdigest.STATS.snapshot()
    assert launches == steps and syncs == steps and traces == 0, (
        "sharded check_and_arm steady state must be 1 launch + 1 scalar "
        f"sync + 0 retraces per step, got {launches}/{syncs}/{traces} "
        f"over {steps} steps")

    # --- donated pair: 2 launches + 1 scalar sync per step --------------
    dstate = campaign.clone(state)
    dstep = jax.jit(raw, donate_argnums=(0,))
    dcanary = ChecksumCanary(dstate, n_slices=n_slices, ctx=ctx)
    for s in range(n_slices):                                # warm/compile
        dcanary.arm_current(s, dstate)
        assert dcanary.check(s, dstate) is None
        dstate, m = dstep(dstate, bfn(s))
    jax.block_until_ready(jax.tree_util.tree_leaves(dstate)[0])
    kdigest.STATS.reset()
    for s in range(steps):
        dcanary.arm_current(s, dstate)
        assert dcanary.check(s, dstate) is None
        dstate, m = dstep(dstate, bfn(s))
    jax.block_until_ready(jax.tree_util.tree_leaves(dstate)[0])
    dl, ds, dt = kdigest.STATS.snapshot()
    assert dl == 2 * steps and ds == steps and dt == 0, (dl, ds, dt)

    # --- in-step fused under donation: 1 COMBINED launch + 1 sync -------
    fstate = campaign.clone(state)
    fcanary = ChecksumCanary(fstate, n_slices=n_slices, ctx=ctx)
    factory = fcanary.fuse_into_step(raw, donate=True)
    warm_s = factory.warm(fstate, bfn(0))
    for s in range(n_slices):                                # settle
        fstate, m, rep = factory.step(s, fstate, bfn(s))
        assert rep is None
    jax.block_until_ready(jax.tree_util.tree_leaves(fstate)[0])
    kdigest.STATS.reset()
    for s in range(n_slices, n_slices + steps):
        fstate, m, rep = factory.step(s, fstate, bfn(s))
        assert rep is None
    jax.block_until_ready(jax.tree_util.tree_leaves(fstate)[0])
    fl, fs, ft = kdigest.STATS.snapshot()
    assert fl == steps and fs == steps and ft == 0, (
        "sharded in-step fused steady state must be 1 combined launch + "
        f"1 scalar sync + 0 retraces per step, got {fl}/{fs}/{ft} over "
        f"{steps} steps")

    # --- byte accounting vs the cost model ------------------------------
    # the DESIGN §5 model: every device packs its LOCAL shard of each
    # leaf tile-aligned (128 KiB tiles), and the global pass is exactly
    # n_shards local passes.  Recompute the prediction independently
    # from the shard shapes and require exact agreement with the plan's
    # accounting.
    TILE = 256 * 128
    local_tiles = sum(
        max(1, -(-int(np.prod(x.sharding.shard_shape(jax.numpy.shape(x)),
                               dtype=np.int64) or 1) // TILE))
        for x in jax.tree_util.tree_leaves(state))
    expected_local = local_tiles * TILE * 4
    assert plan.local_bytes_per_pass == expected_local, (
        plan.local_bytes_per_pass, expected_local)
    assert plan.bytes_per_pass == plan.local_bytes_per_pass * plan.n_shards
    digested_per_step = 2 * plan.bytes_per_pass / n_slices
    # alignment overhead (< 128 KiB/leaf/shard) — reported; it is a fixed
    # byte count, so it amortises to ~1x on production states and only
    # looks large on this CPU smoke state split D ways
    pack_ratio = plan.bytes_per_pass / state_bytes

    return {
        "mesh_shape": dict(mesh.shape),
        "n_shards": plan.n_shards,
        "n_slices": n_slices,
        "steps": steps,
        "oracle_exact": bool(oracle_exact),
        "check_and_arm": {"launches_per_step": launches / steps,
                          "syncs_per_step": syncs / steps,
                          "retraces_per_step": traces / steps,
                          "steps_per_s": steps / wall},
        "donated_pair": {"launches_per_step": dl / steps,
                         "syncs_per_step": ds / steps,
                         "retraces_per_step": dt / steps},
        "fused": {"launches_per_step": fl / steps,
                  "syncs_per_step": fs / steps,
                  "retraces_per_step": ft / steps,
                  "warmup_compiles": factory.n_compiles,
                  "warmup_wall_s": warm_s},
        "state_mb": state_bytes / 1e6,
        "packed_mb_per_pass": plan.bytes_per_pass / 1e6,
        "digested_mb_per_step": digested_per_step / 1e6,
        "per_device_mb_per_step": digested_per_step / plan.n_shards / 1e6,
        "pack_ratio": pack_ratio,
    }


def parity_steady_state(campaign: Campaign, steps: int = 16,
                        n_slices: int = 8) -> Dict:
    """XOR-parity maintenance accounting (the parity-rung contract).

    The parity shard is updated INSIDE the canary's existing launches
    (gated incremental ``old ^ new ^ parity`` in check_and_arm and the
    in-step fused step; rebuild-of-armed-version riding the donated
    pair's arm), so attaching a ParityStore must not change the
    steady-state dispatch/sync/retrace counts of ANY protocol.  All
    hard-asserted, not just reported:

      * fused ``check_and_arm`` + parity: 1 launch + 1 scalar sync;
      * donated arm/check pair + parity: 2 launches + 1 scalar sync;
      * in-step fused under donation + parity: 1 COMBINED launch + 1
        scalar sync;
      * 0 retraces everywhere (the executable caches key on the plan
        object, which is process-cached per tree structure);
      * the incrementally-maintained parity is bit-exact to a
        from-scratch rebuild of the final state;
      * memory cost = parity buffer bytes ~= covered bytes / D.
    """
    from repro.core import ParityStore

    # --- fused check_and_arm with parity riding the launch --------------
    st = campaign.states[0]
    canary = ChecksumCanary(st, n_slices=n_slices)
    pstore = ParityStore(st)
    pstore.build(st, 0)
    canary.attach_parity(pstore)
    for s in range(n_slices):                                # warm/compile
        ns, m = campaign.step(st, campaign.bfn(s))
        assert canary.check_and_arm(s, st, ns) is None
        st = ns
    jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
    kdigest.STATS.reset()
    for s in range(n_slices, n_slices + steps):
        ns, m = campaign.step(st, campaign.bfn(s))
        assert canary.check_and_arm(s, st, ns) is None
        st = ns
    jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
    cl, cs, ct = kdigest.STATS.snapshot()
    assert cl == steps and cs == steps and ct == 0, (
        "check_and_arm with parity attached must stay 1 launch + 1 "
        f"scalar sync + 0 retraces per step, got {cl}/{cs}/{ct} over "
        f"{steps} steps")
    # incremental parity of the final version == from-scratch rebuild
    fresh = ParityStore(st)
    fresh.build(st, 0)
    inc_exact = bool(np.array_equal(np.asarray(pstore.parity),
                                    np.asarray(fresh.parity)))
    assert inc_exact, "incremental parity diverged from rebuild"

    # --- donated pair with parity ---------------------------------------
    dstate = campaign.clone(campaign.states[0])
    dstep = campaign.donated_step()
    dcanary = ChecksumCanary(dstate, n_slices=n_slices)
    dps = ParityStore(dstate)
    dps.build(dstate, 0)
    dcanary.attach_parity(dps)
    for s in range(n_slices):                                # warm/compile
        dcanary.arm_current(s, dstate)
        assert dcanary.check(s, dstate) is None
        dstate, m = dstep(dstate, campaign.bfn(s))
    jax.block_until_ready(jax.tree_util.tree_leaves(dstate)[0])
    kdigest.STATS.reset()
    for s in range(steps):
        dcanary.arm_current(s, dstate)
        assert dcanary.check(s, dstate) is None
        dstate, m = dstep(dstate, campaign.bfn(s))
    jax.block_until_ready(jax.tree_util.tree_leaves(dstate)[0])
    dl, ds, dt = kdigest.STATS.snapshot()
    assert dl == 2 * steps and ds == steps and dt == 0, (
        "donated pair with parity attached must stay 2 launches + 1 "
        f"scalar sync + 0 retraces per step, got {dl}/{ds}/{dt} over "
        f"{steps} steps")

    # --- in-step fused under donation with parity -----------------------
    fstate = campaign.clone(campaign.states[0])
    fcanary = ChecksumCanary(fstate, n_slices=n_slices)
    fps = ParityStore(fstate)
    fps.build(fstate, 0)
    fcanary.attach_parity(fps)
    factory = fcanary.fuse_into_step(campaign.raw_step(), donate=True)
    factory.warm(fstate, campaign.bfn(0))
    for s in range(n_slices):                                # settle
        fstate, m, rep = factory.step(s, fstate, campaign.bfn(s))
        assert rep is None
    jax.block_until_ready(jax.tree_util.tree_leaves(fstate)[0])
    kdigest.STATS.reset()
    for s in range(n_slices, n_slices + steps):
        fstate, m, rep = factory.step(s, fstate, campaign.bfn(s))
        assert rep is None
    jax.block_until_ready(jax.tree_util.tree_leaves(fstate)[0])
    fl, fs_, ft = kdigest.STATS.snapshot()
    assert fl == steps and fs_ == steps and ft == 0, (
        "in-step fused with parity attached must stay 1 combined launch "
        f"+ 1 scalar sync + 0 retraces per step, got {fl}/{fs_}/{ft} "
        f"over {steps} steps")

    covered = sum(
        int(np.prod(pstore.plan.shapes[k]) or 1)
        * np.dtype(pstore.plan.dtypes[k]).itemsize
        for k in pstore.plan.keys)
    state_bytes = sum(x.nbytes
                      for x in jax.tree_util.tree_leaves(campaign.states[0]))
    return {
        "steps": steps,
        "n_shards": pstore.plan.n_shards,
        "incremental_equals_rebuild": inc_exact,
        "check_and_arm": {"launches_per_step": cl / steps,
                          "syncs_per_step": cs / steps,
                          "retraces_per_step": ct / steps},
        "donated_pair": {"launches_per_step": dl / steps,
                         "syncs_per_step": ds / steps,
                         "retraces_per_step": dt / steps},
        "fused": {"launches_per_step": fl / steps,
                  "syncs_per_step": fs_ / steps,
                  "retraces_per_step": ft / steps},
        "parity_memory_bytes": pstore.memory_bytes,
        "state_bytes": state_bytes,
        "memory_overhead": pstore.memory_bytes / state_bytes,
        "covered_bytes": covered,
    }


def run(campaign: Campaign, steps: int = 30) -> Dict:
    base = _loop(campaign, steps, traps=False, canary_k=0, snapshots=False)
    traps = _loop(campaign, steps, traps=True, canary_k=0, snapshots=False)
    snaps = _loop(campaign, steps, traps=True, canary_k=0, snapshots=True)
    k8 = _loop(campaign, steps, traps=True, canary_k=8, snapshots=True)
    k1 = _loop(campaign, steps, traps=True, canary_k=1, snapshots=True)
    # donation mode: the production compilation setting (in-place state
    # update) with the arm/check canary pair
    dbase = _loop(campaign, steps, traps=True, canary_k=0, snapshots=True,
                  donate=True)
    dk8 = _loop(campaign, steps, traps=True, canary_k=8, snapshots=True,
                donate=True)
    # in-step fused detection: the canary rides the donated step's own
    # launch (1 launch + 1 scalar sync per step after K-executable
    # warmup).  The accounting section runs FIRST — it shares the global
    # executable cache with the steps/s loop below, and only the first
    # builder pays (and can report) the real K-compile warmup cost.
    fused = fused_steady_state(campaign)
    dfk8 = _loop(campaign, steps, traps=True, canary_k=8, snapshots=True,
                 donate=True, fused=True)

    # XOR-parity maintenance: hard-asserts that attaching a ParityStore
    # leaves every protocol's launch/sync/retrace counts unchanged
    parity = parity_steady_state(campaign)

    micro = MicroCheckpointer(interval=2)
    micro.snapshot(0, campaign.states[0])
    micro.snapshot(2, campaign.states[0])
    # mesh-sharded section — runs (and hard-asserts its cost contract)
    # only when the process has >1 device, e.g. under
    # XLA_FLAGS=--xla_force_host_platform_device_count=8
    sharded = sharded_steady_state(campaign)
    return {
        "steps_per_s": {"no_detectors": base, "traps_only": traps,
                        "traps+snapshots": snaps,
                        "traps+snapshots+canary_k8": k8,
                        "traps+snapshots+canary_k1": k1,
                        "donated+traps+snapshots": dbase,
                        "donated+traps+snapshots+canary_k8": dk8,
                        "donated+fused+traps+snapshots+canary_k8": dfk8},
        "sharded": sharded,
        "overhead_pct": {
            "traps_only": 100 * (base / traps - 1),
            "traps+snapshots": 100 * (base / snaps - 1),
            "traps+snapshots+canary_k8": 100 * (base / k8 - 1),
            "traps+snapshots+canary_k1": 100 * (base / k1 - 1),
            "donated_canary_k8_vs_donated": 100 * (dbase / dk8 - 1),
            "donated_fused_k8_vs_donated": 100 * (dbase / dfk8 - 1),
        },
        "snapshot_memory_bytes": micro.memory_bytes,
        "digest": digest_throughput(campaign),
        "donation": donation_steady_state(campaign),
        "fused": fused,
        "parity": parity,
        "note": ("canary digests run as Pallas interpret on CPU here — on "
                 "TPU the compiled kernel streams at HBM bandwidth and the "
                 "K=8 rotating canary (one fused launch + one scalar sync "
                 "per step) costs <1% of step time (see DESIGN.md §4.2); "
                 "traps_only is the paper-faithful free-detection "
                 "configuration."),
    }


def render(out: Dict) -> str:
    lines = ["## No-fault overhead (paper Fig 9 analogue)", ""]
    lines.append("| configuration | steps/s | overhead vs bare |")
    lines.append("|---|---|---|")
    sps = out["steps_per_s"]
    lines.append(f"| no detectors | {sps['no_detectors']:.2f} | — |")
    for k in ("traps_only", "traps+snapshots", "traps+snapshots+canary_k8",
              "traps+snapshots+canary_k1"):
        lines.append(f"| {k} | {sps[k]:.2f} "
                     f"| {out['overhead_pct'][k]:+.1f}% |")
    lines.append("")
    d = out["digest"]
    lines.append("### Detection throughput (fused digest engine vs seed "
                 "per-leaf path)")
    lines.append("")
    lines.append("| path | ms/pass | GB/s | launches | syncs |")
    lines.append("|---|---|---|---|---|")
    lines.append(f"| fused single-launch | {d['fused_ms']:.2f} "
                 f"| {d['fused_gbps']:.2f} | 1 | 0-1 |")
    lines.append(f"| seed per-leaf | {d['per_leaf_ms']:.2f} "
                 f"| {d['per_leaf_gbps']:.2f} | {d['n_leaves']} "
                 f"| {d['n_leaves']} |")
    lines.append("")
    lines.append(f"- fused speedup over per-leaf: {d['speedup']:.1f}× on "
                 f"{d['n_leaves']} leaves "
                 f"({d['digested_mb_per_pass']:.1f} MB digested/pass)")
    lines.append(f"- canary check+arm hot path: "
                 f"{d['canary_launches_per_step']} launch, "
                 f"{d['canary_syncs_per_step']} host sync, "
                 f"{d['canary_retraces_per_step']} retraces per step")
    dn = out["donation"]
    lines.append("")
    lines.append("### Donation mode (donate_argnums=(0,): in-place state "
                 "update)")
    lines.append("")
    zero_allocs = (dn["donation_effective"]
                   and dn["pack_buffer_ptr_stable"]
                   and dn["net_new_live_arrays_per_step"] <= 0)
    lines.append(f"- steady-state device allocations/step on the digest "
                 f"path: **{0 if zero_allocs else 'NONZERO'}** "
                 f"(donation consumed the handed-in buffer: "
                 f"{dn['donation_effective']}; packing buffers "
                 f"pointer-stable: {dn['pack_buffer_ptr_stable']}; net "
                 f"live-array growth/step: "
                 f"{dn['net_new_live_arrays_per_step']:g})")
    lines.append(f"- canary pair per step: "
                 f"{dn['canary_launches_per_step']:g} launches "
                 f"(arm: 0 syncs; check: 1 scalar sync → "
                 f"{dn['canary_syncs_per_step']:g} syncs/step), "
                 f"{dn['canary_retraces_per_step']:g} retraces; "
                 f"{dn['digested_mb_per_step']:.1f} MB digested/step "
                 f"at {dn['digest_gbps']:.2f} GB/s")
    k_d = "donated+traps+snapshots"
    k_dk8 = "donated+traps+snapshots+canary_k8"
    d_cost = out["overhead_pct"]["donated_canary_k8_vs_donated"]
    lines.append(f"- donated loop: {sps[k_d]:.2f} steps/s bare vs "
                 f"{sps[k_dk8]:.2f} with canary K=8 "
                 f"({d_cost:+.1f}% canary cost under donation)")
    fu = out["fused"]
    lines.append("")
    lines.append("### In-step fused detection (canary inside the donated "
                 "step; DESIGN.md §4.2)")
    lines.append("")
    lines.append(f"- steady-state hot path: "
                 f"**{fu['launches_per_step']:g} launch/step** (the step's "
                 f"own dispatch carries the check+arm digest), "
                 f"{fu['syncs_per_step']:g} scalar sync/step, "
                 f"{fu['retraces_per_step']:g} retraces/step; digests "
                 f"bit-exact to the per-leaf oracle: {fu['oracle_exact']}")
    lines.append(f"- K-executable warmup: {fu['warmup_compiles']} "
                 f"rotation-specialised compiles in "
                 f"{fu['warmup_wall_s']:.2f} s wall "
                 f"({fu['warmup_compile_s']:.2f} s compiling) for "
                 f"K={fu['n_slices']} — the one-time price of fusing "
                 f"detection into the step")
    k_dfk8 = "donated+fused+traps+snapshots+canary_k8"
    f_cost = out["overhead_pct"]["donated_fused_k8_vs_donated"]
    lines.append(f"- donated loop: {sps[k_dfk8]:.2f} steps/s fused vs "
                 f"{sps[k_dk8]:.2f} with the arm/check pair "
                 f"({f_cost:+.1f}% fused canary cost vs donated bare; "
                 f"{fu['digested_mb_per_step']:.1f} MB digested/step — "
                 f"same bytes as the pair, half its dispatches)")
    lines.append(f"- double-buffered in-HBM snapshot memory: "
                 f"{out['snapshot_memory_bytes']/1e6:.1f} MB "
                 f"(paper: 27 MB fixed)")
    pa = out.get("parity")
    if pa:
        lines.append("")
        lines.append("### XOR parity maintenance (device-resident rung; "
                     "rides the canary's launches)")
        lines.append("")
        ca, dp, pf = pa["check_and_arm"], pa["donated_pair"], pa["fused"]
        lines.append(
            f"- steady state with parity ATTACHED (asserted): "
            f"check_and_arm **{ca['launches_per_step']:g} launch + "
            f"{ca['syncs_per_step']:g} scalar sync**/step; donated pair "
            f"{dp['launches_per_step']:g}/{dp['syncs_per_step']:g}; "
            f"in-step fused **{pf['launches_per_step']:g} combined launch "
            f"+ {pf['syncs_per_step']:g} scalar sync**/step; 0 retraces "
            f"everywhere — parity maintenance adds ZERO dispatches")
        lines.append(
            f"- incremental update bit-exact to a from-scratch rebuild "
            f"after {pa['steps']} steps: "
            f"{pa['incremental_equals_rebuild']}")
        lines.append(
            f"- memory: {pa['parity_memory_bytes']/1e6:.1f} MB parity for "
            f"{pa['state_bytes']/1e6:.1f} MB state "
            f"({100 * pa['memory_overhead']:.1f}% ~= 1/D, D="
            f"{pa['n_shards']}) — the price of reconstructing any single "
            f"lost shard with no snapshot and no replay")
    shd = out.get("sharded")
    lines.append("")
    lines.append("### Mesh-sharded detection (shard-local digests, "
                 "all-reduced fault flag; DESIGN.md §5)")
    lines.append("")
    if shd is None:
        lines.append("- skipped: single-device process (force a CPU mesh "
                     "with XLA_FLAGS=--xla_force_host_platform_device_"
                     "count=8)")
    else:
        ca, fu = shd["check_and_arm"], shd["fused"]
        lines.append(f"- mesh {shd['mesh_shape']} ({shd['n_shards']} "
                     f"shards), K={shd['n_slices']}: per-shard digests "
                     f"bit-identical to the single-device oracle: "
                     f"{shd['oracle_exact']}")
        lines.append(f"- steady state (asserted): check_and_arm "
                     f"**{ca['launches_per_step']:g} launch + "
                     f"{ca['syncs_per_step']:g} scalar sync**/step; "
                     f"donated pair "
                     f"{shd['donated_pair']['launches_per_step']:g}/"
                     f"{shd['donated_pair']['syncs_per_step']:g}; "
                     f"in-step fused (donated) "
                     f"**{fu['launches_per_step']:g} combined launch + "
                     f"{fu['syncs_per_step']:g} scalar sync**/step "
                     f"(warmup {fu['warmup_compiles']} compiles, "
                     f"{fu['warmup_wall_s']:.1f} s); 0 retraces everywhere")
        lines.append(f"- bytes: {shd['state_mb']:.1f} MB state packs to "
                     f"{shd['packed_mb_per_pass']:.1f} MB "
                     f"({shd['pack_ratio']:.2f}x); "
                     f"{shd['digested_mb_per_step']:.2f} MB digested/step "
                     f"total = {shd['per_device_mb_per_step']:.3f} MB/"
                     f"device — each device streams only its addressable "
                     f"1/{shd['n_shards']}; the all-reduced fault flag is "
                     f"the only cross-device traffic on the no-fault path")
    lines.append(f"- {out['note']}")
    return "\n".join(lines)
