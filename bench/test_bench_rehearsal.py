"""Each cell of the benchmark rehearsed end to end on the CPU.

The cells here are defined only in a temporary directory: a copy of
``BENCHMARK.json`` whose cells point at a configuration of the same
family at smoke widths and at traffic files sized for a CPU, with limits
set for that size.  ``run.main`` drives them with the look for a chip
skipped (``require_chip=False``, here only); Pallas kernels run in
interpret mode.  Then the timed path is broken underneath, once for each
fault a cell can have, and ``correct`` has to come out false.
"""

from __future__ import annotations

import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from bench import cells, run as bench_run

SMOKE_CONFIG = {
    "name": "danube-smoke",
    "source": "https://huggingface.co/h2oai/h2o-danube-1.8b-base",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "sliding_window": 64,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "reduced": [],
    "program": {
        "arch": "h2o-danube-1.8b",
        "model": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 256, "sliding_window": 64,
                  "max_position": 512},
        "train": {"microbatch": 8, "remat": "layer"},
    },
}

TRAIN = {"kind": "train", "global_batch": 8, "seq_len": 32,
         "inject_every": 0, "snapshot_interval": 8, "canary_slices": 1,
         "donate": True, "fused_detect": True, "parity": True,
         "setup_steps": 4, "cycle_steps": 8, "trace_steps": 4}
FLIPS = dict(TRAIN, inject_every=3, inject_target="params", cycle_steps=24)
SERVE = {"kind": "serve", "rate_per_s": 40.0,
         "prompt_len": {"median": 12, "sigma": 0.5, "min": 8, "max": 24,
                        "buckets": [8, 16, 24]},
         "output_len": {"median": 6, "sigma": 0.4, "min": 4, "max": 10},
         "slots": 2, "max_len": 40, "block_size": 8, "prefill_chunk": 0,
         "canary_slices": 4, "donate": True, "parity": True,
         "inject_every": 9, "check_requests": 3,
         "trace_start_step": 2, "trace_steps": 4}

#: the serving cell's metrics, as a later BENCHMARK.json would list them
SERVE_METRICS = {
    "end_to_end": [
        {"name": "ttft_ms_p95", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock"},
        {"name": "tpot_ms_p95", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock"}],
    "per_layer": [
        {"name": n, "unit": u, "better": b, "source": s, "layer": l,
         "moves": "tpot_ms_p95"}
        for n, u, b, s, l in (
            ("decode_step_ms.serve", "ms", "lower", "program_span",
             "loop and engine"),
            ("mfu.serve", "%", "higher", "host_clock", "model step"),
            ("gather_roofline.serve", "%", "higher", "device_trace",
             "kernels"),
            ("device_idle.serve", "%", "lower", "device_trace", "device"),
            ("slot_recovery_ms.serve", "ms", "lower", "program_span",
             "recovery"))],
}

#: limits at this size, from CPU readings of sound runs against the
#: faults below (bfloat16 program, float32 reference)
TRAIN_LIMITS = {"loss_gap": 0.002, "grad_gap": 0.02, "update_gap": 0.1}
SERVE_LIMITS = {"logit_gap": 0.2}

CELLS = {"smoke_train": ("train", TRAIN, TRAIN_LIMITS),
         "smoke_flips": ("flips", FLIPS, TRAIN_LIMITS),
         "smoke_serve": ("serve", SERVE, SERVE_LIMITS)}


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    b = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (b / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(cells.HERE, "metrics"), b / "metrics")
    # the CPU has no entry in the peak table; the rehearsal's own copy
    # gets a placeholder so that the per-layer readers run (their values
    # here mean nothing)
    peaks = json.loads(open(os.path.join(cells.HERE, "peaks.json")).read())
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e10, "source": "placeholder for tests"}
    (b / "peaks.json").write_text(json.dumps(peaks))
    (b / "configs" / "danube-smoke.json").write_text(
        json.dumps(SMOKE_CONFIG))
    spec = cells.load_benchmark()
    real = {w["traffic"]: w for w in spec["workloads"]}
    kinds = {"train": "train_8x4096", "flips": "train_8x2048_flip6"}
    rename = {}
    workloads = []
    for name, (kind, traffic, limits) in CELLS.items():
        (b / "traffic" / f"{kind}.json").write_text(json.dumps(traffic))
        (b / "limits" / f"{name}.json").write_text(json.dumps(
            {k: {"limit": v} for k, v in limits.items()}))
        w = real.get(kinds.get(kind), {"chips": 1, "why": "smoke"})
        rename[w.get("name")] = name
        workloads.append(dict(w, name=name, config="danube-smoke",
                              traffic=kind))
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            if "workloads" in m:
                m["workloads"] = [rename[w] for w in m["workloads"]]
        # the serving cell is defined here only, with its metrics
        spec[kind] += [dict(m, workloads=["smoke_serve"])
                       for m in SERVE_METRICS[kind]]
    spec["workloads"] = workloads
    spec["configs"] = [{"name": "danube-smoke", "source": "x",
                        "file": "bench/configs/danube-smoke.json",
                        "reduced": [], "why": "smoke"}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def rehearse(root, name, capsys, trace=0, seed=2 ** 31 + 11):
    rc = bench_run.main(["--workload", name, "--seed", str(seed),
                         "--seconds", "0.05", "--trace", str(trace)],
                        root=root, require_chip=False, compile_cache=False)
    assert rc == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_no_chip_no_run(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--workload", "danube2l_train", "--seed", "1",
                        "--seconds", "1"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_rehearsal(smoke_root, name, capsys):
    line, err = rehearse(smoke_root, name, capsys)
    assert line["correct"], line["checks"]
    spec = cells.load_benchmark(smoke_root)
    want = {m["name"] for m in cells.metrics_for(spec, name, "end_to_end")}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("name", ["smoke_flips", "smoke_serve"])
def test_cell_rehearsal_traced(smoke_root, name, capsys):
    line, _ = rehearse(smoke_root, name, capsys, trace=1)
    assert line["correct"], line["checks"]
    assert line["device"]["window_s"] > 0
    assert "breakdown" in line
    spec = cells.load_benchmark(smoke_root)
    names = {m["name"] for m in cells.metrics_for(spec, name, "per_layer")}
    assert set(line["metrics"]) <= names


# ---------------------------------------------------------------------------
# the timed path broken underneath: correct has to come out false
# ---------------------------------------------------------------------------

def _unchanged_state(monkeypatch):
    """The step returns its state unchanged."""
    import repro.launch.train as T
    real = T.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def frozen(state, batch):
            _, metrics = step(state, batch)
            return jax.tree_util.tree_map(jnp.copy, state), metrics
        return frozen
    monkeypatch.setattr(T, "make_train_step", make)


def _half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    import repro.launch.train as T
    real = T.make_train_step

    def make(cfg, *a, global_batch=0, **kw):
        step = real(cfg, *a, global_batch=global_batch, **kw)

        def half(state, batch):
            cut = jax.tree_util.tree_map(
                lambda x: jnp.concatenate([x[: x.shape[0] // 2]] * 2),
                batch)
            return step(state, cut)
        return half
    monkeypatch.setattr(T, "make_train_step", make)


def _altered_token(monkeypatch):
    """A served token altered where the engine accepts it."""
    from repro.serving import engine as E
    real = E.ServingEngine._accept

    def accept(self, tokens, now_s):
        tokens = tokens.copy()
        tokens[0] = (int(tokens[0]) + 1) % self.m.vocab_size
        return real(self, tokens, now_s)
    monkeypatch.setattr(E.ServingEngine, "_accept", accept)


@pytest.mark.parametrize("name,fault", [
    ("smoke_train", _unchanged_state),
    ("smoke_train", _half_batch),
    ("smoke_serve", _altered_token),
])
def test_broken_path_is_not_correct(smoke_root, name, fault, capsys,
                                    monkeypatch):
    fault(monkeypatch)
    line, _ = rehearse(smoke_root, name, capsys)
    assert not line["correct"], line["checks"]
