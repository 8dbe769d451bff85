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
* ``metrics/<metric>.py``: the reader of one per-layer metric.

A later cell needs new files and entries only; nothing here names a cell.
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
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def arch_config(config: Dict):
    """The program's ``ArchConfig`` for a configuration file: the
    registry's entry for ``program.arch`` with the file's overrides."""
    from repro.configs import get_config
    prog = config["program"]
    cfg = get_config(prog["arch"])
    model = dataclasses.replace(cfg.model, **prog.get("model", {}))
    train = dataclasses.replace(cfg.train, **prog.get("train", {}))
    return dataclasses.replace(cfg, model=model, train=train)


def model_dims(config: Dict) -> Dict:
    """The sizes the yardstick (reference, costs) computes with, read from
    the published keys of a configuration file."""
    heads = config["num_attention_heads"]
    return {
        "layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim",
                               config["hidden_size"] // heads),
        "d_ff": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "window": config.get("sliding_window") or 0,
        "rope_theta": config["rope_theta"],
        "norm_eps": config["rms_norm_eps"],
        "tie": config.get("tie_word_embeddings", False),
    }
