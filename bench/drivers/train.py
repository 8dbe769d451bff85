"""General driver of a training job: ``repro.launch.train.train`` as a
user calls it, timed from outside.

``train()`` sets up and loops in one call, so the window is found through
the narrowest seams the program has: the constructor of its recovery
runtime (the last set-up action before the loop) and the fused step's
``step``/``replay``.  If either seam is gone the run fails: see
``SeamMissing``.

One call, one object: set-up builds the state and the compiled step and
drives it from the seed through its first ``setup_steps`` steps, whose
input states are read back for the comparison with the reference.  The
window opens when the same loop calls step ``setup_steps`` and closes at
the end of the whole cycle (``cycle_steps``: snapshots and faults come
back in the same phase) nearest to ``--seconds``, by raising out of the
loop before the next step runs; the state then handed to that step is the trained
state that the digest oracle reads.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench import digest as hdigest
from bench import trace as btrace


def fused_programs():
    """The fused step's compiled programs (cached while the loop runs)."""
    from repro.core import fused_step
    return [ent[0] for per in fused_step._EXEC_CACHE.values()
            for ent in per.values()]


class WindowClosed(Exception):
    """Raised through ``train()`` once the window has run."""


class SeamMissing(RuntimeError):
    """The program no longer has the seam the window is timed by."""


def host(tree):
    """Host copy through a device temporary: a host view of a live
    donated buffer would veto its donation."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(jnp.array(x, copy=True)), tree)


class Seams:
    """Hooks on the program's loop for the length of one call."""

    def __init__(self, job, traffic: Dict):
        self.job = job
        self.setup = traffic["setup_steps"]
        self.cycle = traffic["cycle_steps"]
        self.trace_steps = traffic.get("trace_steps", 0)
        self.runtime = None
        self.t_runtime = None
        self.t0 = self.t1 = None
        self.captures = {}
        self.losses = {}          # step -> loss of the kept first pass
        self.replayed = []        # (step, loss) of every replayed step
        self.recoveries = []      # (step, seconds, event) in the window
        self.final_state = None
        self.tracer = None
        self.trace_span = None
        self.programs = []

    # -- seams --------------------------------------------------------------

    def install(self):
        import repro.core.fused_step as F
        import repro.launch.train as T
        from repro.core import MicroCheckpointer
        self._saved = (T, T.RecoveryRuntime, F.FusedStepFactory,
                       F.FusedStepFactory.step, F.FusedStepFactory.replay,
                       MicroCheckpointer, MicroCheckpointer.record_iv)
        real_runtime, step, replay, record_iv = (
            T.RecoveryRuntime, F.FusedStepFactory.step,
            F.FusedStepFactory.replay, MicroCheckpointer.record_iv)
        seams = self

        def watchdog(mself, s, iv):
            # every step of every loop records its counters: a loop that
            # never reaches the seams below fails here, not never
            if seams.runtime is None or (seams.t0 is None
                                         and s > seams.setup):
                raise SeamMissing(
                    "train() ran step %d without the window opening: it no "
                    "longer builds its RecoveryRuntime before the loop or "
                    "steps through FusedStepFactory.step" % s)
            return record_iv(mself, s, iv)

        def runtime(*a, **kw):
            rt = real_runtime(*a, **kw)
            seams.on_runtime(rt)
            return rt

        def fused_step(fself, s, state, *args):
            return seams.on_step(step, fself, s, state, *args)

        def fused_replay(fself, s, state, *args):
            out = replay(fself, s, state, *args)
            seams.replayed.append((s, out[1]["loss"]))
            return out

        T.RecoveryRuntime = runtime
        F.FusedStepFactory.step = fused_step
        F.FusedStepFactory.replay = fused_replay
        MicroCheckpointer.record_iv = watchdog

    def uninstall(self):
        T, rt, cls, step, replay, micro, record_iv = self._saved
        T.RecoveryRuntime = rt
        cls.step = step
        cls.replay = replay
        micro.record_iv = record_iv

    def on_runtime(self, rt):
        self.runtime = rt
        self.t_runtime = time.perf_counter()
        recover = rt.recover
        seams = self

        def timed(state, report, s, *a, **kw):
            t = time.perf_counter()
            out = recover(state, report, s, *a, **kw)
            if seams.t0 is not None:
                seams.recoveries.append((s, time.perf_counter() - t, out[1]))
            return out
        rt.recover = timed

    def stop_trace(self, now, ran):
        if self.tracer is not None and self.trace_span is None:
            self.trace_span = (self.t0, now, ran)
            self.tracer.stop()

    def on_step(self, step, fself, s, state, *args):
        now = time.perf_counter()
        if self.t0 is None and s == self.setup:
            self.t0 = now
            if self.job.trace:
                self.tracer = btrace.Recorder(self.job.scratch)
                self.tracer.start()
        if self.t0 is not None:
            ran = s - self.setup
            # close on the whole cycle nearest to --seconds
            if ran and ran % self.cycle == 0 and (now - self.t0) * (
                    1 + self.cycle / (2 * ran)) >= self.job.seconds:
                self.t1 = now
                self.final_state = state
                if self.tracer is not None:
                    self.programs = fused_programs()
                self.stop_trace(now, ran)
                raise WindowClosed()
            if ran == self.trace_steps:
                self.stop_trace(now, ran)
        if s == 0 and 0 not in self.captures:
            self.captures[0] = host(state["params"])
        elif s == 1:
            self.captures[1] = host(state["opt"]["m"])
        elif s == 3:
            self.captures[3] = host(state["params"])
        out = step(fself, s, state, *args)
        if out[2] is None:
            self.losses[s] = out[1]["loss"]
        return out


def spans(traced: bool):
    """The host spans of a traced run, around the calls into each layer
    of the loop."""
    if not traced:
        return contextlib.nullcontext()
    import repro.launch.train as T
    from repro.core import (ChecksumCanary, MicroCheckpointer, ParityStore,
                            RecoveryRuntime)
    from repro.core.fused_step import FusedStepFactory
    return btrace.spans([
        (FusedStepFactory, "step", "step"),
        (FusedStepFactory, "replay", "replay"),
        (RecoveryRuntime, "recover", "recover"),
        (MicroCheckpointer, "snapshot", "snapshot"),
        (ChecksumCanary, "refresh", "canary.refresh"),
        (ParityStore, "rebuild", "parity.rebuild"),
        (T, "inject", "inject"),
    ])


def run(job) -> Dict:
    """Run one training cell; returns the harness's run record."""
    from repro.launch.train import train
    from repro.kernels import digest as kdigest

    t = job.traffic
    cfg = job.arch
    seams = Seams(job, t)
    seams.install()
    try:
        with spans(job.trace):
            train(cfg, steps=10 ** 9, global_batch=t["global_batch"],
                  seq_len=t["seq_len"], seed=job.seed,
                  snapshot_interval=t["snapshot_interval"],
                  inject_every=t["inject_every"],
                  inject_target=t.get("inject_target", "params"),
                  canary_slices=t["canary_slices"], donate=t["donate"],
                  fused_detect=t["fused_detect"], parity=t["parity"],
                  verbose=False)
    except WindowClosed:
        pass
    finally:
        seams.uninstall()
    if seams.runtime is None or seams.t0 is None or seams.t1 is None:
        raise SeamMissing(
            "the window was never opened or closed: train() no longer "
            "builds its RecoveryRuntime or steps through "
            "FusedStepFactory.step, so the timing seam is gone")
    memory_peak = job.memory_peak()

    # -- the window's own numbers ------------------------------------------
    window_s = seams.t1 - seams.t0
    kept = len([s for s in seams.losses if s >= seams.setup])
    tokens = kept * t["global_batch"] * t["seq_len"]
    recovered = [r for r in seams.recoveries if r[2].recovered]
    faults = len(seams.recoveries)
    injected = (len([s for s in range(seams.setup, seams.setup + kept)
                     if s and s % t["inject_every"] == 0])
                if t["inject_every"] else 0)
    out = {
        "t_window": seams.t0,
        "window_s": window_s,
        "attempted": kept,
        "failed": faults - len(recovered),
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "counters": {
            "steps": kept, "tokens": tokens, "faults": faults,
            "recovered": len(recovered),
            "replayed_steps": sum(r[2].steps_replayed for r in recovered),
            "rungs": sorted({r[2].rung for r in recovered}),
        },
    }
    if recovered:
        out["end_to_end"]["recovery_ms_per_fault"] = \
            1e3 * sum(r[1] for r in recovered) / len(recovered)

    # the runtime holds the canary, its packing buffers and the parity:
    # drop it so that the oracle and the reference below have the chip
    seams.runtime = None
    gc.collect()

    # -- recovery: counts, replay bits and the device digest ----------------
    first = {s: np.float32(v) for s, v in seams.losses.items()}
    mismatched = [s for s, v in seams.replayed
                  if s in first and np.float32(v).tobytes()
                  != first[s].tobytes()]
    state = seams.final_state
    seams.final_state = None
    # the canary's own packing buffers go first: the oracle's digest
    # needs a transient buffer of its own as large as the state
    kdigest.clear_plan_cache()
    gc.collect()
    dev = kdigest.plan_for(state).digest_dict(state)
    ref = hdigest.tree_digests(host(state))
    bad_leaves = [k for k in ref if k not in dev or not np.array_equal(
        np.asarray(dev[k]).view(np.uint32), ref[k])]
    checks = [
        ("faults_missed", abs(injected - faults), 0),
        ("faults_unrecovered", faults - len(recovered), 0),
        ("replay_loss_mismatch", len(mismatched), 0),
        ("digest_mismatch", len(bad_leaves), 0),
    ]
    del state, dev
    kdigest.clear_plan_cache()
    gc.collect()

    if seams.tracer is not None:
        t0, t1, n = seams.trace_span
        out["trace"] = seams.tracer.read(seams.programs)
        seams.programs = []
        out["counters"]["traced_steps"] = n
        out["counters"]["traced_s"] = t1 - t0
        out["counters"]["traced_tokens"] = n * t["global_batch"] \
            * t["seq_len"]

    # -- the model step against the reference -------------------------------
    caps = seams.captures
    yard = job.yardstick
    prog = {"losses": [float(seams.losses[s]) for s in range(3)],
            "params0": yard.program_weights(caps[0]),
            "params": yard.program_weights(caps[3]),
            "grad0": {k: v / (1 - yard.ADAM["b1"]) for k, v in
                      yard.program_weights(caps[1]).items()}}
    seams.captures = {}
    refr = job.reference_train(steps=3)
    nums = compare.train_numbers(prog, refr)
    for name, (value, _) in nums.items():
        checks.append((name, value, job.limits[name]["limit"]))
    out["checks"] = checks
    out["memory_peak_bytes"] = memory_peak
    return out
