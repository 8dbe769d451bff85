"""Where the benchmark finds what a cell is made of.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; each
is a data file of its own, found by name:

* ``configs/<config>.json``: the model as it is run (published keys, the
  ones cut, and the program's own settings under ``"program"``);
* ``traffic/<traffic>.json``: the job or the mix of requests, with a
  ``"kind"`` that names the general driver which reads it (``train`` or
  ``serve``);
* ``limits/<workload>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from;
* ``metrics/<metric>.py``: the reader of one per-layer metric;
* ``yardsticks/<yardstick>.py``: what the benchmark knows of a model
  family, named by the configuration's ``"yardstick"`` (``dense`` where
  the key is absent).

A yardstick is one module that imports nothing of the program and
provides:

* ``dims(config) -> dict``: the sizes it computes with, read from the
  configuration's published keys; at least ``layers`` and ``vocab``;
* ``train_readings(dims, seed, batch, seq, steps, quant=None,
  rows=None)``: the plain float32 reference's first ``steps`` training
  steps from the seed's weights and rows, as ``compare.train_numbers``
  reads them (``quant``: the control's precision; ``rows``: the loss
  over the first rows only);
* ``ADAM``: the optimizer settings the reference follows; the program's
  first gradient is read as ``m / (1 - ADAM["b1"])``;
* ``program_weights(params) -> {name: ndarray}``: the program's
  parameter tree under the reference's weight names;
* ``train_flops(dims, batch, seq)``: the operations of one training
  step (remat not counted);
* for a family that serves, ``serve_flops(dims, work)`` and
  ``serve_gaps(dims, seed, sequences, n_prompt, controls=())``.

A later cell, of a new family too, needs new files and entries only;
nothing here names a cell or a family.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Optional[str] = None) -> Dict:
    return _load(os.path.join(root or ROOT, "BENCHMARK.json"))


def workload(spec: Dict, name: str) -> Dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, base: Optional[str] = None) -> Dict:
    return _load(os.path.join(base or HERE, "configs", f"{name}.json"))


def load_traffic(name: str, base: Optional[str] = None) -> Dict:
    return _load(os.path.join(base or HERE, "traffic", f"{name}.json"))


def load_limits(name: str, base: Optional[str] = None) -> Dict:
    return _load(os.path.join(base or HERE, "limits", f"{name}.json"))


def metrics_for(spec: Dict, name: str, kind: str) -> list:
    """The cell's metrics of one kind (``end_to_end`` or ``per_layer``):
    those that list the cell, or list none."""
    return [m for m in spec[kind]
            if name in m.get("workloads", [name])]


def metric_reader(name: str, base: Optional[str] = None):
    """``read(run) -> float | None`` of one per-layer metric."""
    path = os.path.join(base or HERE, "metrics", f"{name}.py")
    return _module("bench_metric_" + name, path).read


def _module(name: str, path: str):
    mod_spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def yardstick(config: Dict, base: Optional[str] = None):
    """The yardstick module the configuration names, loaded by path."""
    name = config.get("yardstick", "dense")
    path = os.path.join(base or HERE, "yardsticks", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"configuration {config.get('name')!r} names yardstick "
            f"{name!r}, and there is no {path}")
    return _module("bench_yardstick_" + name, path)


def arch_config(config: Dict):
    """The program's ``ArchConfig`` for a configuration file: the
    registry's entry for ``program.arch`` with the file's overrides."""
    from repro.configs import get_config
    prog = config["program"]
    cfg = get_config(prog["arch"])
    model = dataclasses.replace(cfg.model, **prog.get("model", {}))
    train = dataclasses.replace(cfg.train, **prog.get("train", {}))
    return dataclasses.replace(cfg, model=model, train=train)
