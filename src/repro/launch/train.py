"""Fault-tolerant training driver — the paper's runtime as a first-class
feature of the training loop.

    PYTHONPATH=src python -m repro.launch.train --arch iterpro-100m --smoke \
        --steps 200 --batch 8 --seq 128 --inject 5

Hot path per step (in order, mirroring the paper's §3.5 design):
    1. step_fn (jitted; pure)                         — the work
    2. free traps on already-computed scalars         — SIGSEGV analogue
    3. rotating checksum canary over 1/K of the state — dormant corruption
    4. micro-checkpoint bookkeeping (bytes)           — Algorithm 2
Everything else (recovery ladder, snapshots restore, disk C/R) is OFF the
hot path and runs only on a FaultReport.

With ``--fused-detect`` steps 1 and 3 are ONE jitted program: the canary
check/arm runs inside the step (core/fused_step.py), so the no-fault hot
path is a single launch + a single scalar sync even under ``--donate``.
"""

from __future__ import annotations

import argparse
import json
import random
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
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
    trap_loss_spike,
    trap_nonfinite,
)
from repro.core.detect import LOSS_WINDOW
from repro.data.pipeline import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_context
from repro.launch.specs import bind_state
from repro.train.loop import (
    make_train_state,
    make_train_step,
)


@dataclass
class LoopReport:
    steps: int = 0
    faults_injected: int = 0
    faults_detected: int = 0
    faults_recovered: int = 0
    losses: List[float] = field(default_factory=list)
    recovery_ms: List[float] = field(default_factory=list)
    step_seconds: List[float] = field(default_factory=list)
    elastic_events: List[Dict] = field(default_factory=list)

    def summary(self) -> Dict:
        out = {
            "steps": self.steps,
            "final_loss": self.losses[-1] if self.losses else None,
            "losses": list(self.losses),
            "faults_injected": self.faults_injected,
            "faults_detected": self.faults_detected,
            "faults_recovered": self.faults_recovered,
            "mean_recovery_ms": float(np.mean(self.recovery_ms))
            if self.recovery_ms else 0.0,
            "mean_step_ms": 1e3 * float(np.mean(self.step_seconds))
            if self.step_seconds else 0.0,
        }
        if self.elastic_events:
            out["elastic_events"] = list(self.elastic_events)
        return out


def batch_for(cfg, pipe, step):
    batch = pipe.batch_at(step)
    m = cfg.model
    if m.n_enc_layers:
        batch = pipe.with_src_embeds(batch, 64, m.frontend_dim, step)
    if m.patch_dim:
        batch = pipe.with_patches(batch, 16, m.patch_dim, step)
    return batch


def _rearm(canary, pstore, state, step: int) -> None:
    """Re-digest the canary's reference and rebuild the parity over a state
    the loop did not step into (recovered or restored)."""
    if canary is not None:
        with obs.span("canary.refresh", step=step):
            canary.refresh(state)
    if pstore is not None:
        with obs.span("parity.rebuild", step=step):
            pstore.rebuild(state, step)


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          seed: int = 0, snapshot_interval: int = 8,
          checkpoint_dir: Optional[str] = None, checkpoint_interval: int = 50,
          inject_every: int = 0, inject_target: str = "params",
          canary_slices: int = 4, detectors: bool = True,
          donate: bool = False, fused_detect: bool = False,
          fused_warm: str = "eager", mesh: Optional[str] = None,
          parity: bool = False, triage: bool = False,
          elastic: bool = False, kill_row_at: Optional[int] = None,
          verbose: bool = True, return_state: bool = False):
    """Run the recovery-wrapped loop; returns the loop report dict, or
    ``(report, final_state)`` with ``return_state=True`` (for callers that
    verify the trained state itself, e.g. against a host digest oracle).

    ``donate=True`` is the production compilation setting: the step is
    jitted with ``donate_argnums=(0,)`` so XLA updates the train state in
    place (half the state HBM).  The resilient path stays donation-safe:
    the canary runs at the pre-step buffer's last readable moment (just
    before the step consumes it) with its double-buffered reference table,
    and on ANY trap recovery pivots to the in-HBM micro-snapshot + IV
    replay rung — the trap path never touches a donated buffer.  With
    ``donate=False`` the loop is bit-identical to the pre-donation driver.

    ``fused_detect=True`` fuses the canary INTO the jitted step
    (``ChecksumCanary.fuse_into_step``; DESIGN.md §4.2 "in-step fused"):
    the input-slice check and the output-slice arm are subcomputations of
    the step itself, so each step is 1 combined launch + 1 scalar sync —
    under donation this halves the dispatch count of the arm/check pair —
    at the cost of ``canary_slices`` rotation-specialised compilations
    (``fused_warm``: ``'eager'`` compiles all K before the first step,
    ``'lazy'`` compiles each rotation on first use).  Detection semantics
    and digests are bit-identical to the unfused paths, which are left
    untouched when the flag is off.

    ``mesh="dp,tp"`` (e.g. ``"4,2"``) runs the WHOLE resilient loop on a
    device mesh (DESIGN.md §5): the state is sharded per
    ``launch/specs.state_shardings`` and pinned there every step, the
    canary goes shard-local (per-device digests + per-device generation
    tables; the one fetched scalar is the all-reduced fault flag),
    snapshots carry per-(leaf, shard) digests, and recovery gains the
    shard_patch rung (restore only the injured shard's addressable
    bytes).  Composes with ``donate``/``fused_detect`` unchanged.

    ``parity=True`` maintains a device-resident XOR parity shard over the
    full state tree (params AND optimizer moments; core/parity.py), kept
    current by the same launch that runs the canary check/arm — no extra
    dispatch, no host traffic.  On a (leaf, shard) fault the recovery
    ladder gains the ``parity_xor`` rung: the injured shard is rebuilt
    from surviving peers + parity in O(bytes/D), digest-certified, with
    zero host-snapshot bytes read and zero replay steps.  Memory cost is
    1/D of the covered state (each device holds 1/D of the parity under
    ``mesh``).  Requires ``detectors=True`` — parity maintenance rides
    the canary's launches and reconstruction certifies against its
    reference digests.

    ``triage=True`` enables recovery rung 0 (``core/recover.py``):
    checksum-attributed faults are classified against the canary's
    reference digest pair BEFORE any repair, and certified-harmless flips
    (dead int8-moment pad bytes, below-epsilon EMA-moment mantissa
    perturbations) are tolerated in place — the digest rows are re-armed
    to the tolerated bits and the loop resumes with zero bytes moved and
    zero replayed steps.  Strictly fault-path-only: the steady state
    keeps the same 1-launch/1-sync/0-retrace contract (asserted by
    ``benchmarks/overhead.py``).  Requires ``detectors=True``.

    ``elastic=True`` (requires ``mesh`` + ``parity`` + ``detectors``)
    arms the HARD-loss path (launch/elastic.py; DESIGN.md §7): the parity
    buffer moves to row-safe placement (sharded over the non-batch mesh
    axes only, so losing a data row never loses the parity that covers
    it), and a ``FaultReport`` carrying ``lost_rows`` routes recovery to
    the ``remesh`` rung — the dead rows' FSDP shards are rebuilt from
    surviving peers + parity, digest-certified against the canary's
    surviving reference rows, the step is re-lowered ONCE onto the
    shrunken mesh, and training resumes at reduced DP width with the
    SAME global batch.  ``kill_row_at=N`` is the chaos drill: before
    step N the loop synthesises an external hard-loss report for the
    highest surviving data row (no process actually dies — the "dead"
    devices are simply never read again).
    """
    with obs.tally() as tally:
        with obs.span("setup", step=0):
            key = jax.random.PRNGKey(seed)
            pipe = TokenPipeline(cfg.model.vocab_size, seq_len,
                                 global_batch, seed=seed)
            ctx = make_context(mesh)
            with obs.span("setup.state"):
                state = make_train_state(cfg, key,
                                         global_batch=global_batch)
                raw_step = make_train_step(cfg, global_batch=global_batch)
                raw_bfn = lambda s: batch_for(cfg, pipe, s)
                # THE mesh-binding recipe (shardings + device_put + layout
                # pin + batch placement) lives in launch/specs.bind_state —
                # the elastic remesh path re-runs the SAME recipe against
                # the degraded context
                state, raw_step, bfn, shardings = bind_state(
                    ctx, cfg, state, raw_step, raw_bfn)
            step_fn = jax.jit(raw_step,
                              donate_argnums=(0,) if donate else ())

            micro = MicroCheckpointer(interval=snapshot_interval, ctx=ctx)
            ckpt = CheckpointManager(checkpoint_dir,
                                     interval=checkpoint_interval) \
                if checkpoint_dir else None
            canary = ChecksumCanary(state, n_slices=canary_slices,
                                    ctx=ctx) if detectors else None
            pstore = None
            if parity:
                if canary is None:
                    raise ValueError(
                        "parity requires detectors=True (parity "
                        "maintenance rides the canary's launches and "
                        "reconstruction certifies against its digests)")
                # elastic hard loss needs row-safe parity placement: the
                # buffer lives on the non-batch mesh axes so a dead data
                # row never takes the parity covering its own shards down
                # with it
                pstore = ParityStore(state, ctx=ctx, row_safe=elastic)
                with obs.span("setup.parity"):
                    pstore.build(state)
                canary.attach_parity(pstore)
            if triage and canary is None:
                raise ValueError(
                    "triage requires detectors=True (rung 0 classifies "
                    "against the canary's digest pair)")
            emgr = None
            elastic_hook = None
            if elastic:
                if ctx is None:
                    raise ValueError(
                        "elastic requires mesh='dp,tp' (a hard loss "
                        "shrinks the data axis of a device mesh)")
                if pstore is None:
                    raise ValueError(
                        "elastic requires parity=True (dead rows' "
                        "shards are rebuilt from the XOR parity)")
                from repro.launch.elastic import ElasticManager
                emgr = ElasticManager(ctx, verbose=verbose)
                elastic_hook = emgr.hook(raw_step=raw_step, cfg=cfg,
                                         batch_fn=raw_bfn, canary=canary,
                                         pstore=pstore, donate=donate)
            if kill_row_at is not None and emgr is None:
                raise ValueError("kill_row_at requires elastic=True")
            fused = None
            if fused_detect:
                if canary is None:
                    raise ValueError(
                        "fused_detect requires detectors=True "
                        "(the canary IS the in-step detector)")
                # the factory jits the RAW step together with the canary
                # check/arm; replay runs through the same executables
                # (``fused.replay``), so a replayed step reproduces the
                # hot path's bits
                fused = canary.fuse_into_step(raw_step, donate=donate,
                                              warm=fused_warm)
                if fused_warm == "eager":
                    # compile all K rotation executables BEFORE the loop
                    # so the first step's wall time doesn't absorb them
                    # ('lazy' keeps the documented pay-per-rotation
                    # behaviour)
                    with obs.span("setup.compile"):
                        fused.warm(state, bfn(0))
            runtime = RecoveryRuntime(
                step_fn=step_fn, batch_fn=bfn,
                iv_registry=promote(cfg, global_batch), micro=micro,
                parity=pstore,
                checkpoint=ckpt.loader(state) if ckpt else None,
                donated=donate, shardings=shardings, canary=canary,
                triage=triage, elastic=elastic_hook,
                replay_step=fused.replay if fused is not None else None)

        rng = random.Random(seed + 7)
        rep = LoopReport()
        # bounded: the spike trap reads only the last LOSS_WINDOW losses
        # (rep.losses keeps the full telemetry trace)
        history = deque(maxlen=LOSS_WINDOW)
        last_inject = -1

        s = 0
        while s < steps:
            if donate and canary is not None and fused is None:
                # donated hot path, arm half: digest slice s%K of the buffer
                # the previous step just produced (one launch, no sync);
                # check(s) below verifies the SAME slice of the SAME buffer
                # version right before the step consumes it
                canary.arm_current(s, state)

            micro.record_iv(s, state["iv"])
            micro.maybe_snapshot(s, state)
            if ckpt:
                ckpt.maybe_save(s, state)

            # -- adversary: single-bit flip before the step (evaluation
            #    only; once per step — a recovery retry must not be
            #    re-hit) --
            if inject_every and s and s % inject_every == 0 \
                    and last_inject != s:
                with obs.span("inject", step=s):
                    plan = sample_plan(rng, state, max_step=1,
                                       target=inject_target)
                    state = inject(state, plan)
                rep.faults_injected += 1
                last_inject = s

            report = None
            if emgr is not None and kill_row_at is not None \
                    and s == kill_row_at and not emgr.dead:
                # chaos drill: the highest surviving data row "dies" here —
                # an external hard-loss report routes straight to the remesh
                # rung; the dead devices are never read again
                target = emgr.kill_target()
                report = FaultReport(
                    s, "external", lost_rows=(target,),
                    detail=f"simulated hard loss of data row {target}")
            if report is None and donate and canary is not None \
                    and fused is None:
                # donated hot path, check half: the step is about to CONSUME
                # the state buffers, so this is their last readable moment —
                # one launch + ONE scalar sync verifies slice s%K against the
                # generation armed at the top of this loop body
                report = canary.check(s, state)

            if report is None:
                with obs.span("step", step=s) as timed:
                    with obs.span("step.batch"):
                        batch = bfn(s)
                    if fused is not None:
                        # in-step fused canary: the check of slice s%K of
                        # the input state and the arm of slice (s+1)%K of
                        # the output ride the step's own launch — 1
                        # combined launch + 1 scalar sync (its span is
                        # ``step.flag_sync``), donated or not; on a report
                        # the new state is corrupt-derived and discarded
                        # below
                        new_state, metrics, report = fused.step(s, state,
                                                                batch)
                    else:
                        new_state, metrics = step_fn(state, batch)
                    jax.block_until_ready(metrics["loss"])
                rep.step_seconds.append(timed.seconds)

                if detectors and report is None:
                    report = trap_nonfinite(s, metrics) or \
                        trap_loss_spike(s, metrics, history)
                    if report is None and not donate and canary is not None \
                            and fused is None:
                        # fused rotating canary — ONE launch + ONE scalar
                        # sync: verify the pre-step state's slice (armed at the end
                        # of an earlier step: was the state rotted while at
                        # rest / in use?) and digest the fresh output's
                        # next-check slice
                        report = canary.check_and_arm(s, state, new_state)

                if report is None:
                    state = new_state
                    loss = float(metrics["loss"])
                    history.append(loss)
                    rep.losses.append(loss)
                    if verbose and s % max(1, steps // 10) == 0:
                        print(f"[train] step {s:5d} loss {loss:.4f}")
                    s += 1
                    rep.steps += 1
                    continue

            # ---------------- recovery path (off hot path) ----------------
            # one ``fault`` span from the report's arrival to the loop's
            # resume: the ladder, the canary refresh and the parity rebuild
            rep.faults_detected += 1
            with obs.span("fault", step=s):
                # in-step fused reports defer leaf attribution to the fault
                # path — materialise it here so the log names the corrupted
                # leaves exactly like the unfused paths (no-op for resolved
                # reports)
                report.resolve()
                if verbose:
                    print(f"[train] FAULT at step {s}: {report}")
                try:
                    state, ev = runtime.recover(state, report, s)
                    rep.faults_recovered += 1
                    rep.recovery_ms.append(1e3 * ev.wall_seconds)
                    resume = getattr(runtime, "pending_remesh", None)
                    if resume is not None:
                        # hard loss: the remesh rung already rebuilt
                        # EVERYTHING against the degraded mesh — swap the
                        # loop's working set wholesale; canary/parity are
                        # freshly armed (no refresh/rebuild: they'd
                        # re-digest what was just certified)
                        runtime.pending_remesh = None
                        ctx = resume.ctx
                        state = resume.state
                        step_fn = resume.step   # AOT-compiled: no retrace
                        raw_step = resume.raw_step
                        bfn = resume.bfn
                        shardings = resume.shardings
                        canary = resume.canary
                        pstore = resume.pstore
                        micro = MicroCheckpointer(interval=snapshot_interval,
                                                  ctx=ctx)
                        runtime.micro = micro
                        # re-close the hook over the new artifacts so a
                        # SECOND loss composes (emgr.ctx already advanced)
                        runtime.elastic = emgr.hook(
                            raw_step=raw_step, cfg=cfg, batch_fn=raw_bfn,
                            canary=canary, pstore=pstore, donate=donate)
                        if fused is not None:
                            # the old fused executables were evicted with
                            # the old mesh; rebuild against the fresh canary
                            fused = canary.fuse_into_step(
                                raw_step, donate=donate, warm=fused_warm)
                            if fused_warm == "eager":
                                fused.warm(state, bfn(s))
                            runtime.replay_step = fused.replay
                        rep.elastic_events.append(resume.event.to_dict())
                    else:
                        # recovery may have produced a whole new state
                        # version (replay/checkpoint rungs): re-digest it
                        # and re-anchor the parity to it
                        _rearm(canary, pstore, state, s)
                    if verbose:
                        print(f"[train] recovered via {ev.rung} in "
                              f"{rep.recovery_ms[-1]:.1f} ms")
                except RecoveryFailed:
                    if ckpt is None:
                        raise
                    state, ck_step = ckpt.restore(state)
                    s = ck_step
                    # restored state == new reference; stale digests would
                    # fire a spurious checksum fault on the next step
                    _rearm(canary, pstore, state, ck_step)
                    if verbose:
                        print(f"[train] cold restore to step {ck_step}")
                # the span ends once the device holds what the loop resumes
                # from, not when the work is enqueued
                jax.block_until_ready((
                    state, canary.reference if canary is not None else None,
                    pstore.parity if pstore is not None else None))

        if ckpt:
            ckpt.wait()
        out = rep.summary()
        out["recovery"] = runtime.summary()
        if ctx is not None:
            out["mesh"] = {"shape": dict(ctx.mesh.shape),
                           "devices": ctx.n_devices}
    out["spans"] = tally.summary()
    return (out, state) if return_state else out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="iterpro-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject", type=int, default=0,
                    help="inject a bit-flip every N steps")
    ap.add_argument("--inject-target", default="params",
                    choices=["params", "opt", "iv"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--snapshot-interval", type=int, default=8)
    ap.add_argument("--canary-slices", type=int, default=4,
                    help="canary rotation period K (1 = digest the whole "
                         "state every step: deterministic same-step "
                         "detection, K× the streaming bytes)")
    ap.add_argument("--donate", action="store_true",
                    help="jit the step with donate_argnums=(0,) — the "
                         "production in-place-update setting; recovery "
                         "pivots to snapshot+replay")
    ap.add_argument("--fused-detect", action="store_true",
                    help="fuse the canary check/arm INTO the jitted step "
                         "(1 combined launch + 1 scalar sync per step; "
                         "K rotation-specialised compilations)")
    ap.add_argument("--fused-warm", default="eager",
                    choices=["eager", "lazy"],
                    help="compile the K fused step executables up front "
                         "(eager) or on first use of each rotation (lazy)")
    ap.add_argument("--mesh", default=None,
                    help="run on a device mesh, e.g. '4,2' = 4-way data x "
                         "2-way model parallel (CPU repro: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8); "
                         "detection goes shard-local, recovery gains the "
                         "shard_patch rung")
    ap.add_argument("--parity", action="store_true",
                    help="keep a device-resident XOR parity shard over the "
                         "full state (1/D memory), updated by the canary's "
                         "own launch; recovery gains the parity_xor rung "
                         "(snapshot-free O(bytes/D) shard reconstruction)")
    ap.add_argument("--triage", action="store_true",
                    help="enable recovery rung 0: classify checksum faults "
                         "against the canary's digest pair and tolerate "
                         "certified-harmless flips in place (dead bytes, "
                         "sub-epsilon moment perturbations) — zero bytes "
                         "moved, zero replay; uncertifiable faults "
                         "escalate unchanged")
    ap.add_argument("--elastic", action="store_true",
                    help="arm the hard-loss remesh path (requires --mesh "
                         "and --parity): row-safe parity placement, and a "
                         "lost_rows fault report shrinks the data axis, "
                         "rebuilds the dead rows' shards from parity, "
                         "re-lowers once and resumes at reduced DP width "
                         "with the same global batch")
    ap.add_argument("--kill-row-at", type=int, default=None, metavar="STEP",
                    help="chaos drill: simulate the hard loss of the "
                         "highest surviving data row just before STEP "
                         "(requires --elastic)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    out = train(cfg, steps=args.steps, global_batch=args.batch,
                seq_len=args.seq, seed=args.seed,
                snapshot_interval=args.snapshot_interval,
                checkpoint_dir=args.ckpt_dir,
                inject_every=args.inject,
                inject_target=args.inject_target,
                canary_slices=args.canary_slices,
                donate=args.donate,
                fused_detect=args.fused_detect,
                fused_warm=args.fused_warm,
                mesh=args.mesh,
                parity=args.parity,
                triage=args.triage,
                elastic=args.elastic,
                kill_row_at=args.kill_row_at)
    print(json.dumps(out, indent=1) if args.json else out)


if __name__ == "__main__":
    main()
