"""General driver of open-loop serving traffic through the program's
``ServingEngine``, built as ``repro.launch.serve.serve`` builds it.

Set-up: the engine (weights made from the seed, paged KV pool, canary,
at-rest parity), its ``warm()``, the prefill program of every prompt
length the traffic uses, and a short run of two requests through a storm
so that admission, eviction and replay have all run once.  The window is
one ``run(requests, inject_every=..., clock=...)`` over the cell's
requests; it closes when the last request has finished.

The traffic is the same set of sizes and gaps for every seed, in another
order: prompt lengths, output lengths and gaps between arrivals are the
quantiles of their distributions at ``(i + 0.5) / n``, each shuffled by
the seed.  Prompt tokens are drawn from the seed.
"""

from __future__ import annotations

import contextlib
import gc
import random
import statistics
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench import trace as btrace


def _quantiles(n: int, dist: Dict) -> np.ndarray:
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = dist["median"] * np.exp(dist["sigma"] * np.asarray(z))
    return np.clip(x, dist["min"], dist["max"])


def plan_requests(traffic: Dict, seed: int, seconds: float,
                  vocab: int) -> List[Dict]:
    """The cell's requests: ``prompt`` (token ids), ``max_new`` and
    ``arrival_s`` (seconds after the window opens)."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    buckets = np.asarray(traffic["prompt_len"]["buckets"])
    plen = buckets[np.searchsorted(
        buckets, np.ceil(_quantiles(n, traffic["prompt_len"])))]
    olen = np.round(_quantiles(n, traffic["output_len"])).astype(int)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / traffic["rate_per_s"]
    rng = np.random.default_rng(seed)
    plen, olen, gaps = (rng.permutation(a) for a in (plen, olen, gaps))
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [{"prompt": rng.integers(0, vocab, int(p)).astype(np.int32),
             "max_new": int(o), "arrival_s": float(a)}
            for p, o, a in zip(plen, olen, arrivals)]


def percentile(values, pct: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), pct))


class StepSeam:
    """Wraps the engine's step for the traced part of the window and
    counts the model work in it."""

    def __init__(self, eng, start: int, steps: int, tracer):
        self.eng, self.start, self.stop = eng, start, start + steps
        self.tracer = tracer
        self.n = 0
        self.span = None
        self.work = {"decode_tokens": 0, "decode_context": 0,
                     "prefill": []}
        self._step = eng.engine_step
        self._prefill = eng._prefill
        eng.engine_step = self.step
        eng._prefill = self.prefill

    def active(self) -> bool:
        return self.start <= self.n < self.stop

    def prefill(self, params, batch):
        if self.active():
            self.work["prefill"].append(int(batch["tokens"].shape[1]))
        return self._prefill(params, batch)

    def step(self):
        if self.n == self.start:
            self.tracer.start()
            self.t0 = time.perf_counter()
        if self.active():
            for rq in self.eng._by_slot.values():
                if not rq.forced:
                    self.work["decode_tokens"] += 1
                    self.work["decode_context"] += len(rq.prompt) \
                        + len(rq.log)
        out = self._step()
        self.n += 1
        if self.n == self.stop:
            jax.block_until_ready(self.eng.tok)
            self.span = time.perf_counter() - self.t0
            self.tracer.stop()
        return out

    def close(self):
        if self.span is None and self.n > self.start:
            self.span = time.perf_counter() - self.t0
            self.tracer.stop()


def warm(eng, prompt_lengths, Request) -> None:
    """Compile what the window will run: every prefill length, and one
    small run through a storm (admission, decode, eviction, replay)."""
    from repro.serving.engine import ServingReport
    eng.warm()
    for p in sorted(set(prompt_lengths)):
        logits, _ = eng._prefill(
            eng.params, {"tokens": jnp.zeros((1, p), jnp.int32)})
        jax.block_until_ready(logits)
    p = min(prompt_lengths)
    reqs = [Request(rid=-1 - i, prompt=np.zeros(p, np.int32),
                    max_new_tokens=12, arrival_s=0.0) for i in range(2)]
    eng.run(reqs, inject_every=5, inject_rng=random.Random(0))
    eng.report = ServingReport(n_slots=eng.S)


def sample(done: List[int], per: Dict, injured, k: int, seed: int):
    """``k`` finished requests drawn from the seed: the one that served
    the most tokens, up to two that a fault touched, the rest at
    random."""
    rng = random.Random(seed)
    longest = max(done, key=lambda r: (per[r]["n_out"], r))
    pick = [longest]
    hurt = sorted(set(injured) & set(done) - {longest})
    pick += rng.sample(hurt, min(2, len(hurt)))
    rest = sorted(set(done) - set(pick))
    pick += rng.sample(rest, min(k - len(pick), len(rest)))
    return pick


def run(job, controls=()) -> Dict:
    """Run one serving cell; returns the harness's run record.
    ``controls``: lower precisions whose gaps are read too (the control
    readings; the benchmark's own runs read none)."""
    from repro.kernels import digest as kdigest
    from repro.serving import Request, ServingEngine

    t = job.traffic
    plan = plan_requests(t, job.seed, job.seconds, job.dims["vocab"])
    eng = ServingEngine(
        job.arch, n_slots=t["slots"], max_len=t["max_len"],
        canary_slices=t["canary_slices"], donate=t["donate"],
        seed=job.seed, max_replays=10 ** 6, block_size=t["block_size"],
        prefill_chunk=t["prefill_chunk"], parity=t["parity"])
    warm(eng, [len(r["prompt"]) for r in plan], Request)
    reqs = [Request(rid=i, prompt=r["prompt"], max_new_tokens=r["max_new"],
                    arrival_s=r["arrival_s"]) for i, r in enumerate(plan)]
    seam, host_spans = None, contextlib.nullcontext()
    if job.trace:
        seam = StepSeam(eng, t["trace_start_step"], t["trace_steps"],
                        btrace.Recorder(job.scratch))
        host_spans = btrace.spans([
            (eng, "engine_step", "engine_step"),
            (eng, "_prefill", "prefill"),
            (eng, "admit", "admit"),
            (eng, "handle_fault", "handle_fault"),
            (eng, "corrupt_slot", "inject"),
        ])
    t0 = time.perf_counter()

    def clock():
        return time.perf_counter() - t0

    with host_spans:
        rep = eng.run(reqs, inject_every=t["inject_every"],
                      inject_rng=random.Random(job.seed), clock=clock)
    window_s = time.perf_counter() - t0
    if seam is not None:
        seam.close()
    memory_peak = job.memory_peak()

    per = rep.per_request
    inf = float("inf")
    ttft, tpot = [], []
    for i in range(len(reqs)):
        r = per.get(i)
        if r is None or r["dropped"]:
            ttft.append(inf)
            tpot.append(inf)
            continue
        ttft.append(r["t_first_s"] - r["arrival_s"])
        if r["n_out"] > 1:
            tpot.append((r["t_done_s"] - r["t_first_s"])
                        / (r["n_out"] - 1))
    done = [i for i in range(len(reqs))
            if i in per and not per[i]["dropped"]]
    short = [i for i in done if per[i]["n_out"] != reqs[i].max_new_tokens]
    faults = rep.faults_detected
    out = {
        "t_window": t0,
        "window_s": window_s,
        "attempted": len(reqs),
        "failed": len(reqs) - len(done),
        "end_to_end": {"ttft_ms_p95": 1e3 * percentile(ttft, 95),
                       "tpot_ms_p95": 1e3 * percentile(tpot, 95)},
        "counters": {
            "requests": len(reqs), "completed": len(done),
            "tokens_out": rep.tokens_out, "engine_steps": rep.engine_steps,
            "decode_ms_sum": float(sum(rep.decode_ms)),
            "faults_injected": rep.faults_injected,
            "faults_detected": faults,
            "faults_recovered": rep.faults_recovered,
            "recovery_ms_sum": float(sum(rep.recovery_ms)),
            "recoveries": len(rep.recovery_ms),
            "replay_tokens": rep.replay_tokens,
            "ttft_ms_p50": 1e3 * percentile(ttft, 50),
            "tpot_ms_p50": 1e3 * percentile(tpot, 50) if tpot else 0.0,
        },
    }
    if seam is not None and seam.span:
        from repro.serving import engine as E
        out["trace"] = seam.tracer.read(
            [ent[0] for ent in E._EXEC_CACHE.values()])
        out["counters"]["traced_s"] = seam.span
        out["counters"]["traced_flops"] = job.yardstick.serve_flops(
            job.dims, seam.work)
        out["counters"]["traced_steps"] = seam.n - seam.start

    # -- correctness: counts, then served tokens against the reference ------
    pick = sample(done, per, rep.injured_rids, t["check_requests"],
                  job.seed)
    seqs = [np.concatenate([reqs[i].prompt, np.asarray(reqs[i].log,
                                                       np.int32)])
            for i in pick]
    n_prompt = [len(reqs[i].prompt) for i in pick]
    out["counters"]["checked_tokens"] = int(sum(
        len(s) - p for s, p in zip(seqs, n_prompt)))
    checks = [("requests_dropped", len(reqs) - len(done), 0),
              ("requests_short", len(short), 0),
              ("faults_missed", rep.faults_injected - faults, 0)]
    # drop every reference to the engine so that its pool, canary and
    # parity leave the chip before the reference runs
    del eng, rep, seam, host_spans
    kdigest.clear_plan_cache()
    gc.collect()
    gaps = job.yardstick.serve_gaps(job.dims, job.seed, seqs, n_prompt,
                                    controls=controls)
    checks.append(("logit_gap", compare.widest(g["served"] for g in gaps),
                   job.limits["logit_gap"]["limit"]))
    out["checks"] = checks
    out["control_gaps"] = {q: compare.widest(g[q] for g in gaps)
                           for q in controls}
    out["memory_peak_bytes"] = memory_peak
    return out
