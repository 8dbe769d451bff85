"""Bring-up smoke of the resilient main path on TPU.

    python chip_smoke.py             # one chip: train + serve phases
    python chip_smoke.py --chips 4   # four chips: elastic (2,2)->(1,2) resume

One process, no JAX children.  It refuses to run unless JAX's first device
is a TPU, then drives the normal entry points (``repro.launch.train.train``
and ``repro.launch.serve.serve``) at the full width of ``iterpro-100m``
with random weights made from a seed, and holds every run to an oracle:

* train: ``donate``, ``fused_detect``, ``parity``, ``canary_slices=1``;
  a run with injected bit flips must detect and certify-recover every one
  and reproduce the fault-free run's loss trajectory bit for bit, and the
  device digest of its final state must equal the host digest oracle;
* serve: paged KV with a fault storm and at-rest parity; no request is
  dropped and every request the storm did not touch produces exactly the
  fault-free run's tokens;
* ``--chips 4``: ``train`` on a (2,2) FSDP mesh with parity and elastic
  recovery takes one shard-attributed bit flip (repaired in place by
  ``parity_xor``/``shard_patch``) and then loses data row 1; it must
  resume on (1,2) with zero disk restores and finish with the state and
  loss trajectory of a never-failed run that continues on (1,2) from the
  pre-loss state (the chaos drill's oracle).

Every phase also checks that each Pallas kernel of its path was lowered to
a Mosaic ``tpu_custom_call`` — none ran in interpret mode.  Per-phase wall
time, compile seconds and peak device bytes go to earlier lines; the last
line is the device record ``{"ok": true, "device": {...}}``.  Any failed
check raises, so the script exits non-zero and prints no result.  These
numbers are a bring-up smoke, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import re
import sys
import tempfile
import time
from contextlib import contextmanager

import jax
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "iterpro-100m"
#: batch x seq of the one-chip train phase.  A compile of the fused
#: step (canary K=1 + parity, donated) for one described v5e chip needs
#: 3.64 GiB of aliased arguments + 8.85 GiB of temporaries at 4 x 1024:
#: 12.5 GiB of the chip's 16 GiB.
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_INJECT = 4, 1024, 8, 3
SERVE_REQUESTS, SERVE_PROMPT, SERVE_GEN, SERVE_SLOTS, SERVE_INJECT = \
    8, 128, 32, 4, 40
#: four-chip elastic drill: (2,2) mesh, one bit flip before step 3, data
#: row 1 lost before step 4, resume on (1,2)
MESH4, DRILL_BATCH, DRILL_SEQ, DRILL_STEPS, DRILL_INJECT, DRILL_KILL = \
    "2,2", 8, 512, 6, 3, 4

#: kernels each phase's programs must contain as compiled Mosaic calls
TRAIN_KERNELS = {"_row_checksum_kernel", "_xor_update_kernel",
                 "_xor_fold_kernel"}
SERVE_KERNELS = {"_row_checksum_kernel", "_gather_block_kernel",
                 "_xor_fold_kernel"}
DRILL_KERNELS = {"_row_checksum_kernel"}


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def device_record() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """The first thing ``main`` does: no TPU, no run."""
    rec = device_record()
    if rec["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{rec['platform']!r}); nothing was run")
    return rec


def full_config(fsdp: bool = False):
    from repro.configs import get_config
    cfg = get_config(ARCH)
    if fsdp:
        cfg = dataclasses.replace(
            cfg, sharding=dataclasses.replace(cfg.sharding, fsdp=True))
    return cfg


def _bits_equal(a, b) -> bool:
    a, b = np.atleast_1d(np.asarray(a)), np.atleast_1d(np.asarray(b))
    return a.dtype == b.dtype and a.shape == b.shape \
        and np.array_equal(a.view(np.uint8), b.view(np.uint8))


def _trees_bit_identical(x, y) -> bool:
    fx, tx = jax.tree_util.tree_flatten(jax.device_get(x))
    fy, ty = jax.tree_util.tree_flatten(jax.device_get(y))
    return tx == ty and all(_bits_equal(a, b) for a, b in zip(fx, fy))


# ---------------------------------------------------------------------------
# kernel audit: the lowered StableHLO of every program a phase compiled
# ---------------------------------------------------------------------------

_KERNEL = re.compile(r'tpu_custom_call.*?kernel_name = "([^"]+)"')


@contextmanager
def kernel_audit():
    """Dump the StableHLO of every program compiled inside the block.

    Yields a dict that, on exit, maps each dumped module name to the set
    of Mosaic kernel names it calls (``kernels``) and lists the modules
    that hold a ``pallas_call`` but no ``tpu_custom_call`` — kernels that
    were interpreted (``interpreted``).  JAX dumps before its persistent
    cache lookup, so cache hits are audited too."""
    out = {"kernels": {}, "interpreted": []}
    prev_to = jax.config.read("jax_dump_ir_to")
    prev_modes = jax.config.read("jax_dump_ir_modes")
    with tempfile.TemporaryDirectory() as d:
        jax.config.update("jax_dump_ir_to", d)
        jax.config.update("jax_dump_ir_modes", "stablehlo")
        try:
            yield out
        finally:
            jax.config.update("jax_dump_ir_to", prev_to)
            jax.config.update("jax_dump_ir_modes", prev_modes)
            for path in sorted(glob.glob(os.path.join(d, "*.mlir"))):
                name = re.sub(r"^jax_ir\d+_|_compile\.mlir$", "",
                              os.path.basename(path))
                with open(path) as f:
                    text = f.read()
                names = set(_KERNEL.findall(text))
                if names:
                    out["kernels"].setdefault(name, set()).update(names)
                elif "pallas_call" in text:
                    out["interpreted"].append(name)


def check_kernels(audit: dict, expected: set, phase: str) -> dict:
    """No interpreted kernel, and every expected kernel compiled."""
    check(not audit["interpreted"],
          f"{phase}: programs ran Pallas kernels in interpret mode: "
          f"{audit['interpreted']}")
    seen = set().union(*audit["kernels"].values()) if audit["kernels"] \
        else set()
    check(expected <= seen, f"{phase}: kernels not compiled for the chip: "
          f"{sorted(expected - seen)} (compiled: {sorted(seen)})")
    return {m: sorted(k) for m, k in audit["kernels"].items()}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_phase(cfg, *, batch: int, seq: int, steps: int,
                inject_every: int, seed: int = 0) -> dict:
    """Fault-free run, then a run with bit flips every ``inject_every``
    steps, both with the production settings; returns the checked
    figures."""
    from repro.kernels import digest as kdigest
    from repro.launch.train import train

    kw = dict(steps=steps, global_batch=batch, seq_len=seq, seed=seed,
              donate=True, fused_detect=True, parity=True, canary_slices=1,
              verbose=False)
    clean = train(cfg, **kw)
    faulty, state = train(cfg, inject_every=inject_every, return_state=True,
                          **kw)

    injected = faulty["faults_injected"]
    check(injected == (steps - 1) // inject_every,
          f"train: {injected} flips injected")
    check(faulty["faults_detected"] == injected,
          f"train: detected {faulty['faults_detected']} of {injected}")
    rec = faulty["recovery"]
    check(faulty["faults_recovered"] == injected
          and rec["recovered"] == rec["events"] == injected,
          f"train: recovered {faulty['faults_recovered']} of {injected} "
          f"({rec})")
    for run in (clean, faulty):
        check(len(run["losses"]) == steps
              and all(np.isfinite(run["losses"])),
              f"train: losses {run['losses']}")
    check(faulty["losses"] == clean["losses"],
          f"train: trajectory after recovery differs from the fault-free "
          f"run: {faulty['losses']} vs {clean['losses']}")

    # the chip's digest of the trained state against the host oracle
    device = kdigest.plan_for(state).digest_dict(state)
    host = kdigest.host_tree_checksums(jax.device_get(state))
    check(device.keys() == host.keys()
          and all(np.array_equal(device[k], host[k]) for k in host),
          "train: device digest of the final state differs from the host "
          "oracle")
    return {"batch": batch, "seq": seq, "steps": steps,
            "faults": injected, "by_rung": rec["by_rung"],
            "final_loss": faulty["final_loss"],
            "mean_step_ms_clean": clean["mean_step_ms"],
            "leaves_digested": len(host)}


def serve_phase(cfg, *, n_requests: int, prompt_len: int, gen: int,
                slots: int, inject_every: int, seed: int = 0) -> dict:
    """Fault-free run, then the same requests under a fault storm and an
    at-rest weight flip; returns the checked figures."""
    from repro.launch.serve import serve

    kw = dict(n_requests=n_requests, prompt_len=prompt_len,
              gen_tokens=gen, seed=seed, n_slots=slots, donate=True,
              parity=True, verbose=False)
    clean = serve(cfg, **kw)
    storm = serve(cfg, inject_every=inject_every, **kw)

    for run in (clean, storm):
        check(run["dropped"] == 0 and run["completed"] == n_requests,
              f"serve: completed {run['completed']}, dropped "
              f"{run['dropped']}")
        check(all(len(t) == gen for t in run["outputs"].values()),
              "serve: a request stopped short")
    faults = storm["faults"]
    check(faults["injected"] > 0
          and faults["detected"] == faults["injected"],
          f"serve: faults {faults}")
    check(storm["parity"]["repaired"] == 1 and not storm["parity"]["failed"],
          f"serve: at-rest scrub {storm['parity']}")
    healthy = [r for r in clean["outputs"] if r not in storm["injured"]]
    check(healthy, "serve: the storm touched every request")
    check(all(storm["outputs"][r] == clean["outputs"][r] for r in healthy),
          "serve: a healthy request's tokens differ from the fault-free run")
    injured_exact = sum(storm["outputs"][r] == clean["outputs"][r]
                        for r in storm["injured"])
    return {"requests": n_requests, "prompt": prompt_len, "gen": gen,
            "faults": faults, "healthy_bit_identical": len(healthy),
            "injured": len(storm["injured"]),
            "injured_bit_identical": int(injured_exact),
            "p50_decode_ms": storm["p50_decode_ms"]}


def elastic_phase(cfg, *, mesh: str, batch: int, seq: int, steps: int,
                  inject_every: int, kill_at: int, seed: int = 0) -> dict:
    """The four-chip drill through ``train``, against the chaos drill's
    oracle: a never-failed run to ``kill_at`` that continues on the
    degraded mesh from its pre-loss state."""
    from repro.data.pipeline import TokenPipeline
    from repro.launch.mesh import make_context
    from repro.launch.specs import bind_state
    from repro.launch.train import batch_for, train
    from repro.train.loop import make_train_step

    check(kill_at < steps and inject_every < kill_at
          and 2 * inject_every >= steps,
          "elastic: one flip before the loss, none after")
    kw = dict(global_batch=batch, seq_len=seq, seed=seed, mesh=mesh,
              parity=True, elastic=True, canary_slices=1, verbose=False,
              return_state=True)
    drill, state = train(cfg, steps=steps, inject_every=inject_every,
                         kill_row_at=kill_at, **kw)
    pre, pre_state = train(cfg, steps=kill_at, **kw)

    # oracle continuation on the degraded mesh from the pre-loss state
    ctx = make_context(mesh).degrade([drill["elastic_events"][0]
                                      ["lost_rows"][0]])
    pipe = TokenPipeline(cfg.model.vocab_size, seq, batch, seed=seed)
    ob = bind_state(ctx, cfg, pre_state,
                    make_train_step(cfg, global_batch=batch),
                    lambda s: batch_for(cfg, pipe, s))
    ostep = jax.jit(ob.step)
    ost, oracle_losses = ob.state, list(pre["losses"])
    for s in range(kill_at, steps):
        ost, m = ostep(ost, ob.bfn(s))
        oracle_losses.append(float(m["loss"]))

    [ev] = drill["elastic_events"]
    rungs = drill["recovery"]["by_rung"]
    check(drill["faults_injected"] == 1
          and drill["faults_detected"] == drill["faults_recovered"] == 2,
          f"elastic: faults {drill['faults_injected']} injected, "
          f"{drill['faults_detected']} detected, "
          f"{drill['faults_recovered']} recovered")
    check(rungs.get("remesh") == 1
          and rungs.get("parity_xor", 0) + rungs.get("shard_patch", 0) == 1,
          f"elastic: rungs {rungs}")
    check(ev["disk_restores"] == 0 and ev["uncertified_blocks"] == 0
          and ev["blocks_reconstructed"] > 0,
          f"elastic: remesh event {ev}")
    shape = {k: int(v) for k, v in drill["mesh"]["shape"].items()}
    check(shape == {"data": 1, "model": 2}, f"elastic: resumed on {shape}")
    check(drill["losses"] == oracle_losses,
          f"elastic: losses {drill['losses']} vs oracle {oracle_losses}")
    check(_trees_bit_identical(state, ost),
          "elastic: final state differs from the oracle continuation")
    return {"mesh": mesh, "resumed_on": shape, "batch": batch, "seq": seq,
            "steps": steps, "by_rung": rungs,
            "blocks_reconstructed": ev["blocks_reconstructed"],
            "certified_blocks": ev["certified_blocks"],
            "final_loss": drill["final_loss"]}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

class CompileClock:
    """Backend compile seconds, summed from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

        def listen(name, secs, **_):
            if name == self.EVENT:
                self.seconds += secs
        jax.monitoring.register_event_duration_secs_listener(listen)


def peak_bytes() -> int:
    """Largest ``peak_bytes_in_use`` over the devices (None off-chip): the
    process's running peak, so a later phase reports at least an earlier
    one's."""
    stats = [d.memory_stats() for d in jax.devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats if s]
    return max(peaks) if peaks else None


def run_phase(name: str, clock: CompileClock, kernels: set, fn, *a, **kw):
    c0, t0 = clock.seconds, time.perf_counter()
    with kernel_audit() as audit:
        figures = fn(*a, **kw)
    wall = time.perf_counter() - t0
    programs = check_kernels(audit, kernels, name)
    print(json.dumps({"phase": name, "wall_s": wall,
                      "compile_s": clock.seconds - c0,
                      "peak_bytes_in_use": peak_bytes(),
                      "kernel_programs": len(programs),
                      **figures}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip elastic drill")
    args = ap.parse_args(argv)

    # libtpu logs under /tmp unless told otherwise; read when JAX first
    # initialises its backend, which require_tpu does
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    rec = require_tpu()
    print(f"device: {rec['kind']} x{rec['count']} ({rec['platform']})",
          flush=True)
    check(rec["count"] >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX has "
          f"{rec['count']}")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()

    if args.chips == 4:
        run_phase("elastic", clock, DRILL_KERNELS, elastic_phase,
                  full_config(fsdp=True), mesh=MESH4, batch=DRILL_BATCH,
                  seq=DRILL_SEQ, steps=DRILL_STEPS,
                  inject_every=DRILL_INJECT, kill_at=DRILL_KILL)
    else:
        cfg = full_config()
        run_phase("train", clock, TRAIN_KERNELS, train_phase, cfg,
                  batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS,
                  inject_every=TRAIN_INJECT)
        from repro.kernels import digest as kdigest
        kdigest.clear_plan_cache()       # release the train packing buffers
        run_phase("serve", clock, SERVE_KERNELS, serve_phase, cfg,
                  n_requests=SERVE_REQUESTS, prompt_len=SERVE_PROMPT,
                  gen=SERVE_GEN, slots=SERVE_SLOTS,
                  inject_every=SERVE_INJECT)
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
