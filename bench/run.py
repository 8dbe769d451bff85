"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic, its limits and its per-layer
metric readers are found by name under ``bench/`` (see ``cells.py``).
The traffic's ``kind`` names the general driver in ``bench/drivers/``.
With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part
of the window and from the program's counters.

The run refuses (a non-zero exit, no result) where JAX finds no TPU or fewer
chips than the cell asks for.  Every number that decides ``correct`` is
printed beside its limit as the last lines of standard error, and under
``checks`` as the last key of the result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_record() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where the backend
    keeps no such count)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class Job:
    """Everything a driver needs for one run of one cell."""

    def __init__(self, spec, name, seed, seconds, trace, scratch,
                 base=None):
        self.spec = spec
        self.base = base
        self.workload = cells.workload(spec, name)
        self.name = name
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.scratch = scratch
        self.config = cells.load_config(self.workload["config"], base)
        self.traffic = cells.load_traffic(self.workload["traffic"], base)
        self.limits = cells.load_limits(name, base)
        self.dims = cells.model_dims(self.config)
        self.arch = cells.arch_config(self.config)

    memory_peak = staticmethod(memory_peak)

    def reference_train(self, steps: int, **kw):
        from bench import reference
        t = self.traffic
        return reference.train_readings(self.dims, self.seed,
                                        t["global_batch"], t["seq_len"],
                                        steps=steps, **kw)


def enable_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` (the program's own rule), every program kept, so that
    only a cell's first run in a checkout compiles."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def per_layer(spec, name, run, base=None) -> dict:
    out = {}
    for m in cells.metrics_for(spec, name, "per_layer"):
        value = cells.metric_reader(m["name"], base)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(spec, name, run, setup_s) -> dict:
    values = dict(run["end_to_end"], setup_s=setup_s)
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cells.metrics_for(spec, name, "end_to_end")}


def peaks(kind: str, base=None) -> dict:
    with open(os.path.join(base or HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def result_line(job, run, setup_s, device) -> dict:
    checks = run["checks"]
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    if job.trace:
        tr = run.get("trace") or {}
        dev["busy_s"] = tr.get("busy_s", 0.0)
        dev["window_s"] = tr.get("window_s", 0.0)
        run = dict(run, dims=job.dims, traffic=job.traffic,
                   chips=job.workload["chips"],
                   peak=peaks(device["kind"], job.base))
        metrics = per_layer(job.spec, job.name, run, job.base)
    else:
        metrics = end_to_end(job.spec, job.name, run, setup_s)
    line = {"correct": correct, "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": dev}
    if job.trace and run.get("trace"):
        line["breakdown"] = run["trace"]["breakdown"]
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, v, lim in checks}
    return line


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         compile_cache: bool = True) -> int:
    """``root``: the directory holding ``BENCHMARK.json`` and, under
    ``bench/``, the cells' files.  ``require_chip=False`` lets a test
    rehearse a run on the CPU."""
    args = parse(argv)
    spec = cells.load_benchmark(root)
    base = os.path.join(root, "bench")
    job_spec = cells.workload(spec, args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    device = device_record()
    if require_chip and (device["platform"] != "tpu"
                         or device["count"] < job_spec["chips"]):
        raise NoChip(f"bench: {args.workload} needs {job_spec['chips']} "
                     f"TPU chip(s); JAX found {device['count']} "
                     f"{device['platform']} device(s); nothing was run")
    if compile_cache:
        enable_cache()
    with tempfile.TemporaryDirectory(prefix="bench-") as scratch:
        job = Job(spec, args.workload, args.seed, args.seconds, args.trace,
                  scratch, base)
        driver = importlib.import_module(
            f"bench.drivers.{job.traffic['kind']}")
        run = driver.run(job)
    setup_s = run["t_window"] - T_START
    line = result_line(job, run, setup_s, device)
    print("bench: " + json.dumps({"window_s": run["window_s"],
                                  "setup_s": setup_s,
                                  "counters": run["counters"]}),
          file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
