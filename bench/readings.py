"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/readings.py train <cell> --seeds 1 2 3
    python3 bench/readings.py serve <cell> --seeds 1 2 3 --seconds 20

``train``: at the cell's own size, the reference against itself computed
in the control's precision (float8 operands, for a bfloat16
configuration) and against itself with half of the batch left out (the
mean taken over the rest); each prints the three training numbers.  A
step that returns its state unchanged reads 1 on ``update_gap`` by its
definition and needs no run.

``serve``: the cell's own traffic for a short window, then for the same
prompts and served tokens both the program's widest gap and the
control's: at each position, how far the token that float8 puts first
lies below the float32 best.  One process for all seeds.

The benchmark's own runs run none of this.  Not a test: it needs the
chip and the cell's full size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import cells, compare  # noqa: E402
from bench import run as bench_run  # noqa: E402

CONTROL = "float8_e4m3fn"


def train(job):
    ref = job.reference_train()
    out = {}
    for name, kw in (("control", {"quant": CONTROL}),
                     ("half_batch",
                      {"rows": job.traffic["global_batch"] // 2})):
        got = job.reference_train(**kw)
        out[name] = {k: v for k, (v, _) in
                     compare.train_numbers(got, ref).items()}
    return out


def serve(job):
    from bench.drivers import serve as driver
    rec = driver.run(job, controls=(CONTROL,))
    checks = {n: v for n, v, _ in rec["checks"]}
    return {"program_logit_gap": checks["logit_gap"],
            "control_logit_gap": rec["control_gaps"][CONTROL],
            "checked_tokens": rec["counters"]["checked_tokens"],
            "counts": {k: checks[k] for k in checks if k != "logit_gap"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kind", choices=("train", "serve"))
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    device = bench_run.device_record()
    if device["platform"] != "tpu":
        raise SystemExit("readings: JAX found no TPU; nothing was run")
    bench_run.enable_cache()
    spec = cells.load_benchmark()
    with tempfile.TemporaryDirectory(prefix="readings-") as scratch:
        for seed in args.seeds:
            job = bench_run.Job(spec, args.cell, seed, args.seconds, 0,
                                scratch)
            out = (train if args.kind == "train" else serve)(job)
            print(json.dumps({"cell": args.cell, "seed": seed,
                              "device": device, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
