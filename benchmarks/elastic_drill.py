"""Elastic hard-loss drill — downtime-to-resume vs checkpoint restart.

    PYTHONPATH=src python -m benchmarks.elastic_drill --smoke

Kills a data row of an 8-device (4, 2) mesh mid-run and measures what the
remesh rung (DESIGN.md §7) actually costs:

* **downtime to resume** — last healthy step to first post-loss step:
  survivor-honest gather + XOR parity reconstruction of the dead rows'
  FSDP shards + ONE re-lower on the degraded (3, 2) mesh,
* **bytes moved** — reconstructed (parity) vs re-gathered (replicated)
  bytes, against the full state size a disk restore would move,
* **the strawman** — a from-checkpoint restart on the SAME degraded mesh:
  device_put of the full host checkpoint + re-lower + replay of the steps
  since the last snapshot (the paper's cold-restart cost floor; real
  restarts add scheduler/requeue time on top).

Two contracts are HARD-ASSERTED, not just reported (overhead.py-style):

* ``disk_restores == 0`` and ``uncertified_blocks == 0`` on the remesh
  event — recovery read parity + survivors only, and every surviving
  block was digest-certified against the canary's surviving rows;
* post-remesh steady state is EXACTLY 1 logical canary launch + 1 scalar
  sync + 0 digest retraces per step — the resumed loop kept the fused
  observability contract, and the AOT resume step cannot retrace.

``--out`` writes machine-readable ``BENCH_elastic.json`` so the elastic
downtime trajectory is tracked across PRs.
"""

from __future__ import annotations

import os

# must be set before jax initialises its backends
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import json
import time
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.core.detect import ChecksumCanary, FaultReport
from repro.core.icp import promote
from repro.core.microcheckpoint import MicroCheckpointer
from repro.core.parity import ParityStore
from repro.core.recover import RecoveryRuntime
from repro.data.pipeline import TokenPipeline
from repro.distributed.context import DistContext
from repro.kernels import digest as kdigest
from repro.launch.elastic import ElasticManager
from repro.launch.mesh import make_mesh
from repro.launch.specs import bind_state
from repro.train.loop import make_train_state, make_train_step

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                           "BENCH_elastic.json")


def _state_bytes(state) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(state))


def run(*, arch: str = "iterpro-100m", smoke: bool = True,
        steps: int = 10, kill_at: int = 5, ckpt_every: int = 4,
        global_batch: int = 12, seq_len: int = 32, kill_row: int = 3,
        pure_dp: bool = False, seed: int = 0,
        steady_steps: int = 4) -> Dict:
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8")
    assert 0 < kill_at < steps
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    if not pure_dp:
        # force FSDP so the dead row's shards exercise the parity
        # reconstruction path (pure DP degenerates to re-gather)
        cfg = dataclasses.replace(
            cfg, sharding=dataclasses.replace(cfg.sharding, fsdp=True))
    B, S = global_batch, seq_len

    ctx = DistContext.for_mesh(make_mesh((4, 2), ("data", "model")))
    pipe = TokenPipeline(cfg.model.vocab_size, S, B, seed=seed)
    state = make_train_state(cfg, jax.random.PRNGKey(seed), global_batch=B)
    raw_bfn = lambda s: pipe.batch_at(s)
    state, raw, bfn, sh = bind_state(
        ctx, cfg, state, make_train_step(cfg, global_batch=B), raw_bfn)
    step = jax.jit(raw)
    canary = ChecksumCanary(state, n_slices=1, ctx=ctx)
    pstore = ParityStore(state, ctx=ctx, row_safe=True)
    pstore.build(state)
    canary.attach_parity(pstore)
    emgr = ElasticManager(ctx)
    runtime = RecoveryRuntime(
        step_fn=step, batch_fn=bfn, iv_registry=promote(cfg, B),
        micro=MicroCheckpointer(interval=ckpt_every, ctx=ctx),
        parity=pstore, shardings=sh, canary=canary,
        elastic=emgr.hook(raw_step=raw, cfg=cfg, batch_fn=raw_bfn,
                          canary=canary, pstore=pstore))

    # ---- healthy phase, snapshotting the restart strawman's checkpoint
    ckpt_step, ckpt_host = 0, jax.tree_util.tree_map(np.asarray, state)
    step_walls = []
    for s in range(kill_at):
        if s and s % ckpt_every == 0:
            ckpt_step = s
            ckpt_host = jax.tree_util.tree_map(np.asarray, state)
        t0 = time.perf_counter()
        ns, m = step(state, bfn(s))
        assert canary.check_and_arm(s, state, ns) is None
        jax.block_until_ready(ns["step"] if "step" in ns else
                              jax.tree_util.tree_leaves(ns)[0])
        step_walls.append(time.perf_counter() - t0)
        state = ns
    total_bytes = _state_bytes(state)

    # ---- the hard loss -------------------------------------------------
    report = FaultReport(kill_at, "external", lost_rows=(kill_row,),
                         detail=f"drill: data row {kill_row} lost")
    t_loss = time.perf_counter()
    state, rev = runtime.recover(state, report, kill_at)
    resume = runtime.pending_remesh
    assert resume is not None and rev.rung == "remesh"
    ev = resume.event
    assert ev.disk_restores == 0, "remesh path touched a disk checkpoint"
    assert ev.uncertified_blocks == 0, (
        f"{ev.uncertified_blocks} surviving blocks failed digest "
        f"certification")

    # first post-loss step closes the downtime window
    st = resume.state
    ns, m = resume.step(st, resume.bfn(kill_at))
    assert resume.canary.check_and_arm(kill_at, st, ns) is None
    jax.block_until_ready(jax.tree_util.tree_leaves(ns)[0])
    downtime_to_resume = time.perf_counter() - t_loss
    st = ns

    # ---- run out the schedule on the degraded mesh ---------------------
    for s in range(kill_at + 1, steps):
        ns, m = resume.step(st, resume.bfn(s))
        assert resume.canary.check_and_arm(s, st, ns) is None
        st = ns
    final_loss = float(m["loss"])

    # ---- hard-assert the post-remesh steady state: 1/1/0 ---------------
    jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
    kdigest.STATS.reset()
    for s in range(steps, steps + steady_steps):
        ns, m = resume.step(st, resume.bfn(s))
        assert resume.canary.check_and_arm(s, st, ns) is None
        st = ns
    jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
    launches, syncs, traces = kdigest.STATS.snapshot()
    assert launches == steady_steps and syncs == steady_steps \
        and traces == 0, (
            "post-remesh steady state must be 1 logical launch + 1 "
            f"scalar sync + 0 retraces per step, got {launches}/{syncs}/"
            f"{traces} over {steady_steps} steps")

    # ---- the strawman: from-checkpoint restart on the degraded mesh ----
    # full-state device_put + re-lower + replay of the steps lost since
    # the last snapshot.  The remesh path's re-lower already warmed XLA's
    # autotuning for this (mesh, program), so this strawman is a LOWER
    # bound on a cold restart — which only strengthens the comparison.
    t0 = time.perf_counter()
    rb = bind_state(resume.ctx, cfg, ckpt_host, raw, raw_bfn)
    rstep = jax.jit(rb.step)
    compiled = rstep.lower(rb.state, rb.bfn(ckpt_step)).compile()
    t_bind = time.perf_counter() - t0
    rst = rb.state
    for s in range(ckpt_step, kill_at):
        rst, _ = compiled(rst, rb.bfn(s))
    jax.block_until_ready(jax.tree_util.tree_leaves(rst)[0])
    restart_wall = time.perf_counter() - t0

    return {
        "config": {"arch": arch, "smoke": smoke, "steps": steps,
                   "kill_at": kill_at, "kill_row": kill_row,
                   "ckpt_every": ckpt_every, "global_batch": B,
                   "seq_len": S, "pure_dp": pure_dp, "seed": seed,
                   "mesh": {"data": 4, "model": 2},
                   "degraded_mesh": dict(resume.ctx.mesh.shape)},
        "event": ev.to_dict(),
        "downtime_to_resume_s": downtime_to_resume,
        "reconstruct_s": ev.reconstruct_seconds,
        "relower_s": ev.relower_seconds,
        "bytes_reconstructed": ev.bytes_reconstructed,
        "bytes_regathered": ev.bytes_regathered,
        "state_bytes": total_bytes,
        "reconstructed_fraction":
            ev.bytes_reconstructed / total_bytes if total_bytes else 0.0,
        "restart_baseline": {
            "ckpt_step": ckpt_step,
            "replay_steps": kill_at - ckpt_step,
            "bind_and_compile_s": t_bind,
            "wall_s": restart_wall,
            "bytes_moved": total_bytes,
        },
        "speedup_vs_restart":
            restart_wall / downtime_to_resume if downtime_to_resume else 0.0,
        "healthy_step_ms": 1e3 * float(np.mean(step_walls))
        if step_walls else 0.0,
        "steady_state": {"launches_per_step": launches / steady_steps,
                         "syncs_per_step": syncs / steady_steps,
                         "retraces": traces},
        "final_loss": final_loss,
        "disk_restores": 0,                        # asserted above
    }


def bench_record(out: Dict) -> Dict:
    """The compact cross-PR trajectory record (BENCH_elastic.json)."""
    ev = out["event"]
    return {
        "downtime_to_resume_s": out["downtime_to_resume_s"],
        "reconstruct_s": out["reconstruct_s"],
        "relower_s": out["relower_s"],
        "bytes_reconstructed": out["bytes_reconstructed"],
        "bytes_regathered": out["bytes_regathered"],
        "state_bytes": out["state_bytes"],
        "blocks_reconstructed": ev["blocks_reconstructed"],
        "certified_blocks": ev["certified_blocks"],
        "uncertified_blocks": ev["uncertified_blocks"],
        "restart_baseline_s": out["restart_baseline"]["wall_s"],
        "speedup_vs_restart": out["speedup_vs_restart"],
        "steady_state_launches_per_step":
            out["steady_state"]["launches_per_step"],
        "steady_state_retraces": out["steady_state"]["retraces"],
        "disk_restores": out["disk_restores"],
        "old_dp": ev["old_dp"],
        "new_dp": ev["new_dp"],
    }


def write_bench(out: Dict, path: str = DEFAULT_OUT) -> str:
    path = os.path.abspath(path)
    with open(path, "w") as f:
        json.dump(bench_record(out), f, indent=1)
        f.write("\n")
    return path


def render(out: Dict) -> str:
    c, ev, rb = out["config"], out["event"], out["restart_baseline"]
    lines = ["## Elastic hard-loss drill (remesh rung vs restart)", ""]
    lines.append(
        f"{c['arch']}{' smoke' if c['smoke'] else ''}, mesh "
        f"{c['mesh']['data']}x{c['mesh']['model']} -> "
        f"{out['config']['degraded_mesh']}, row {c['kill_row']} killed at "
        f"step {c['kill_at']}/{c['steps']}, global batch {c['global_batch']}"
        f" kept")
    lines.append("")
    lines.append("| path | wall (s) | bytes moved |")
    lines.append("|---|---|---|")
    lines.append(
        f"| remesh rung (resume) | {out['downtime_to_resume_s']:.2f} | "
        f"{out['bytes_reconstructed'] + out['bytes_regathered']} |")
    lines.append(
        f"| checkpoint restart + replay {rb['replay_steps']} steps | "
        f"{rb['wall_s']:.2f} | {rb['bytes_moved']} |")
    lines.append("")
    lines.append(
        f"- downtime to resume {out['downtime_to_resume_s']:.2f} s = "
        f"reconstruct {out['reconstruct_s']:.2f} s + re-lower "
        f"{out['relower_s']:.2f} s + first degraded step")
    lines.append(
        f"- reconstructed {ev['blocks_reconstructed']} blocks / "
        f"{out['bytes_reconstructed']} B from XOR parity "
        f"({100 * out['reconstructed_fraction']:.2f}% of the "
        f"{out['state_bytes']} B state); re-gathered "
        f"{ev['leaves_regathered']} replicated leaves / "
        f"{out['bytes_regathered']} B")
    lines.append(
        f"- certification: {ev['certified_blocks']} surviving blocks "
        f"digest-certified, {ev['uncertified_blocks']} failures "
        f"(asserted 0); disk restores: {out['disk_restores']} "
        f"(asserted 0)")
    ss = out["steady_state"]
    lines.append(
        f"- post-remesh steady state (asserted): "
        f"{ss['launches_per_step']:g} launch + {ss['syncs_per_step']:g} "
        f"sync + {ss['retraces']} retraces per step at dp={ev['new_dp']}")
    moved = out["bytes_reconstructed"] + out["bytes_regathered"]
    lines.append(
        f"- speedup vs checkpoint restart: "
        f"{out['speedup_vs_restart']:.1f}x wall (restart here is a warm "
        f"lower bound: same-process XLA, zero requeue time; at CPU-smoke "
        f"scale both windows are compile-dominated — the scale-relevant "
        f"ratio is bytes moved, {moved} vs {rb['bytes_moved']} = "
        f"{rb['bytes_moved'] / moved:.1f}x less traffic)")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="iterpro-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--kill-at", type=int, default=5)
    ap.add_argument("--kill-row", type=int, default=3)
    ap.add_argument("--ckpt-every", type=int, default=4,
                    help="restart strawman's snapshot interval")
    ap.add_argument("--batch", type=int, default=12)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--pure-dp", action="store_true",
                    help="keep the arch's fsdp=False: exercises the "
                         "re-gather path instead of parity reconstruction")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="path for BENCH_elastic.json ('' to skip)")
    args = ap.parse_args()

    out = run(arch=args.arch, smoke=args.smoke, steps=args.steps,
              kill_at=args.kill_at, kill_row=args.kill_row,
              ckpt_every=args.ckpt_every, global_batch=args.batch,
              seq_len=args.seq, pure_dp=args.pure_dp, seed=args.seed)
    print(render(out))
    if args.out:
        path = write_bench(out, args.out)
        print(f"\nwrote {path}")


if __name__ == "__main__":
    main()
