"""Find the knee of a serving cell: the highest arrival rate it sustains.

    python3 bench/knee.py <cell> --rates 1 2 3 4 --seconds 30 --seed 7

One engine, built and warmed once, serves the cell's traffic at each
rate in turn (the same sizes, the rate alone changed).  For each rate it
prints the completed tokens per second, the TTFT percentiles, and how far
the queue grew: the mean wait before admission of the last quarter of
arrivals against the first quarter's.  A rate is sustained while that
ratio stays near one and every request is done within a few seconds of
the last arrival.  Run once when a serving cell is defined; the cell then
fixes its rate in its traffic file.  Not a test: it needs the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from bench import cells  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.drivers import serve as driver  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    device = bench_run.device_record()
    if device["platform"] != "tpu":
        raise SystemExit("knee: JAX found no TPU; nothing was run")
    bench_run.enable_cache()
    from repro.serving import Request, ServingEngine
    from repro.serving.engine import ServingReport
    spec = cells.load_benchmark()
    job = bench_run.Job(spec, args.cell, args.seed, args.seconds, 0, "")
    t = job.traffic
    plans = {r: driver.plan_requests(dict(t, rate_per_s=r), args.seed,
                                     args.seconds, job.dims["vocab"])
             for r in args.rates}
    eng = ServingEngine(
        job.arch, n_slots=t["slots"], max_len=t["max_len"],
        canary_slices=t["canary_slices"], donate=t["donate"],
        seed=args.seed, max_replays=10 ** 6, block_size=t["block_size"],
        prefill_chunk=t["prefill_chunk"], parity=t["parity"])
    driver.warm(eng, sorted({len(r["prompt"]) for p in plans.values()
                             for r in p}), Request)
    for rate in args.rates:
        eng.report = ServingReport(n_slots=eng.S)
        reqs = [Request(rid=i, prompt=r["prompt"],
                        max_new_tokens=r["max_new"],
                        arrival_s=r["arrival_s"])
                for i, r in enumerate(plans[rate])]
        t0 = time.perf_counter()
        rep = eng.run(reqs, inject_every=t["inject_every"],
                      inject_rng=random.Random(args.seed),
                      clock=lambda: time.perf_counter() - t0)
        wall = time.perf_counter() - t0
        per = rep.per_request
        wait = [per[i]["t_admit_s"] - per[i]["arrival_s"]
                for i in range(len(reqs))]
        ttft = [per[i]["t_first_s"] - per[i]["arrival_s"]
                for i in range(len(reqs))]
        q = max(1, len(reqs) // 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs), "wall_s": wall,
            "drain_s": wall - max(r.arrival_s for r in reqs),
            "tokens_per_s": rep.tokens_out / wall,
            "ttft_ms_p50": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_ms_p95": 1e3 * float(np.percentile(ttft, 95)),
            "wait_growth": (float(np.mean(wait[-q:])) + 1e-3)
            / (float(np.mean(wait[:q])) + 1e-3),
            "decode_ms_p50": float(np.median(rep.decode_ms)),
            "faults": rep.faults_detected, "dropped": rep.dropped,
            "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
