"""The yardstick checked on the CPU: the reference against the program at
smoke widths, the controls against the limits, the traffic generator,
the operation and byte counts, and the trace reduction."""

from __future__ import annotations

import dataclasses
import math
import os

import jax
import numpy as np
import pytest

from bench import cells, compare, cost, reference
from bench import trace as btrace
from bench.drivers import serve as serve_driver
from bench.drivers import train as train_driver
from bench.test_bench_rehearsal import (SERVE, SERVE_LIMITS, SMOKE_CONFIG,
                                        TRAIN_LIMITS)

SEED = 2 ** 31 + 5
DIMS = cells.model_dims(SMOKE_CONFIG)


@pytest.fixture(scope="module")
def arch():
    return cells.arch_config(SMOKE_CONFIG)


def test_reference_weights_are_the_programs(arch):
    from repro.models.registry import get_model
    prog = get_model(arch.model).init(arch.model, jax.random.PRNGKey(SEED))
    ours = reference.flat(reference.init_params(DIMS, SEED))
    theirs = train_driver.program_weights(prog)
    assert sorted(ours) == sorted(theirs)
    for k in ours:
        a, b = np.asarray(ours[k]), theirs[k]
        assert a.dtype == b.dtype and np.array_equal(
            a.view(np.uint16), b.view(np.uint16)), k


def test_reference_rows_are_the_pipelines():
    from repro.data.pipeline import TokenPipeline
    pipe = TokenPipeline(DIMS["vocab"], 32, 8, seed=SEED)
    for step in (0, 3):
        ours = reference.batch_at(SEED, step, 8, 32, DIMS["vocab"])
        theirs = pipe.batch_at(step)
        for k in ("tokens", "targets"):
            assert np.array_equal(ours[k], theirs[k])


def test_reference_loss_matches_program_in_float32(arch):
    """The program computing in float32 on the same bfloat16 weights
    gives the reference's loss to float32 rounding."""
    from repro.models.registry import get_model
    m32 = dataclasses.replace(arch.model, compute_dtype="float32")
    model = get_model(m32)
    params = model.init(m32, jax.random.PRNGKey(SEED))
    b = reference.batch_at(SEED, 0, 8, 32, DIMS["vocab"])
    with jax.default_matmul_precision("highest"):
        prog, _ = model.train_loss(params, m32, b, remat=False)
    ref = reference.init_params(DIMS, SEED)
    ours = np.mean([float(reference.seq_loss(ref, b["tokens"][i],
                                             b["targets"][i], DIMS))
                    for i in range(8)])
    assert abs(float(prog) - ours) / ours < 1e-5


def test_training_control_fails_a_limit():
    """The reference computed with float8 operands, in the program's
    place, reads over the smoke size's limit on at least one number."""
    ref = reference.train_readings(DIMS, SEED, 8, 32)
    ctl = reference.train_readings(DIMS, SEED, 8, 32, quant="float8_e4m3fn")
    nums = compare.train_numbers(ctl, ref)
    assert any(v > TRAIN_LIMITS[k] for k, (v, _) in nums.items()), nums
    same = compare.train_numbers(ref, ref)
    assert all(v == 0.0 for v, _ in same.values())


def test_serving_control_fails_the_limit():
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, DIMS["vocab"], 40).astype(np.int32)
            for _ in range(3)]
    gaps = reference.serve_gaps(DIMS, SEED, seqs, [16, 16, 16],
                                controls=("float8_e4m3fn",))
    assert compare.widest(g["float8_e4m3fn"] for g in gaps) \
        > SERVE_LIMITS["logit_gap"]


def test_same_sizes_for_every_seed():
    a = serve_driver.plan_requests(SERVE, 1, 2.0, 256)
    b = serve_driver.plan_requests(SERVE, 2 ** 33 + 1, 2.0, 256)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert len(a) == len(b) == round(2.0 * SERVE["rate_per_s"])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert all(len(r["prompt"]) in SERVE["prompt_len"]["buckets"]
               for r in a)
    gaps = lambda plan: sorted(np.diff([r["arrival_s"] for r in plan]))
    assert np.isclose(sum(gaps(a)), sum(gaps(b)), rtol=0.5)


def test_operation_counts():
    d = cells.model_dims(cells.load_config("h2o-danube-1.8b-2l"))
    per_layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert cost.matmul_params(d) == 2 * per_layer + 2560 * 8000
    assert cost.attended_pairs(4, 0) == 10
    assert cost.attended_pairs(6, 4) == 10 + 2 * 4
    f = cost.train_flops(d, 8, 4096)
    assert math.isclose(f / (8 * 4096), 6 * cost.matmul_params(d)
                        + 3 * 4 * 32 * 80 * 2 * 4097 / 2, rel_tol=1e-9)
    assert cost.prefill_flops(d, 1) == 2 * cost.matmul_params(d) \
        + 4 * 32 * 80 * 2


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

E = btrace.Event


def test_busy_is_the_union_of_op_intervals():
    evs = [E("a", 0, 10, {}), E("b", 5, 15, {}), E("c", 20, 30, {})]
    assert btrace.busy_ns(evs) == 25
    assert btrace.gaps(evs, 0, 40) == [(15, 20), (30, 40)]


def test_idle_gaps_are_named_by_the_covering_span():
    tr = {"devices": {0: [E("op", 0, 10, {}), E("op", 50, 60, {})]},
          "host": [E("snapshot", 12, 45, {}), E("step", 0, 11, {})]}
    out = btrace.summarize(tr, ())
    assert out["busy_s"] == pytest.approx(20e-9)
    assert out["window_s"] == pytest.approx(60e-9)
    assert out["breakdown"]["idle_gaps"][0][0] == "snapshot"


def test_kernel_time_and_roofline_from_named_ops():
    stats = {"hlo_module": "jit_fused", "hlo_op": "fusion.7"}
    tr = {"devices": {0: [E("fusion.7", 0, 1000, stats),
                          E("fusion", 1000, 3000, {})]}, "host": []}
    hlo = ("HloModule jit_fused, entry_computation_layout={}\n"
           "  %fusion.7 = s32[2,256,2]{2,1,0:T(8,128)} custom-call("
           "s32[2,256,128]{2,1,0} %bitcast.1), custom_call_target="
           "\"tpu_custom_call\", backend_config={\"name\": "
           "\"_row_checksum_kernel\"}\n")
    sites = btrace.hlo_sites([hlo])
    assert sites[("jit_fused", "fusion.7")]["bytes"] == 2 * 256 * 130 * 4
    out = btrace.summarize(tr, sites)
    k = btrace.kernel_stats(out, ("_row_checksum_kernel",))
    k = k["_row_checksum_kernel"]
    assert k["seconds"] == pytest.approx(1e-6) and k["calls"] == 1
    run = {"trace": out, "peak": {"hbm_bytes_per_s": 8.19e11}}
    assert btrace.roofline_share(run, ("_row_checksum_kernel",)) \
        == pytest.approx(100.0 * 2 * 256 * 130 * 4 / 8.19e11 / 1e-6)
    assert btrace.roofline_share(run, ("_gather_block_kernel",)) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace of `danube2l_train`'s steps 4-11 as recorded
    on one TPU v5e chip, gzipped."""
    import gzip
    import shutil
    here = os.path.join(os.path.dirname(__file__), "testdata")
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(here, "danube2l_train.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return btrace.summarize(btrace.load(str(path)))


def test_recorded_trace_busy_idle_and_gaps(recorded):
    assert recorded["busy_s"] == pytest.approx(9.0513, abs=1e-3)
    assert recorded["window_s"] == pytest.approx(16.9429, abs=1e-3)
    label, secs = recorded["breakdown"]["idle_gaps"][0]
    assert label == "snapshot" and secs == pytest.approx(6.7248, abs=1e-3)
    assert len(recorded["breakdown"]["device_ops"]) == 10


def test_recorded_trace_kernel_by_call_site(recorded):
    """The trace names a Mosaic call by its instruction; the kernel's name
    comes from its call site in the compiled program."""
    assert btrace.kernel_stats(recorded, ("_row_checksum_kernel",)) == {}
    nbytes = 32943 * 256 * (128 + 2) * 4
    sites = {("jit_fused", "fused.2"): {"text": "_row_checksum_kernel",
                                        "bytes": nbytes,
                                        "result_bytes": 0}}
    run = {"trace": dict(recorded, sites=sites),
           "peak": {"hbm_bytes_per_s": 8.19e11}}
    k = btrace.kernel_stats(run["trace"], ("_row_checksum_kernel",))
    k = k["_row_checksum_kernel"]
    assert k["calls"] == 8 and k["seconds"] == pytest.approx(0.16339,
                                                             abs=1e-4)
    share = btrace.roofline_share(run, ("_row_checksum_kernel",))
    assert share == pytest.approx(100 * 8 * nbytes / 8.19e11 / 0.163392,
                                  rel=1e-3)
    assert 0 < share < 100
