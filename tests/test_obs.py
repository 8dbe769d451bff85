"""Spans (``repro.obs``): nesting, the bounded ring, the profiler's clock,
and the spans ``train()`` records at each layer boundary."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.detect import ChecksumCanary
from repro.kernels import digest as dg


def _since(first_id):
    return [r for r in obs.records() if r.id >= first_id]


def _next_id():
    with obs.span("probe.mark") as sp:
        pass
    return sp.id + 1


def test_spans_nest_with_parent_and_step():
    with obs.span("outer", step=7) as outer:
        with obs.span("inner", bytes=12) as inner:
            with obs.span("leaf", step=9) as leaf:
                pass
    assert outer.parent is None
    assert inner.parent == outer.id and leaf.parent == inner.id
    assert (outer.step, inner.step, leaf.step) == (7, 7, 9)
    assert inner.attrs == {"bytes": 12}
    assert outer.start <= inner.start <= leaf.start <= leaf.end \
        <= inner.end <= outer.end
    recs = obs.records()
    # a record is kept when its span closes: children first
    assert [r.name for r in recs[-3:]] == ["leaf", "inner", "outer"]
    with obs.span("alone") as alone:
        pass
    assert alone.parent is None and alone.step == 0


def test_span_records_even_when_the_block_raises():
    with pytest.raises(KeyError):
        with obs.span("raises", step=3):
            raise KeyError("x")
    last = obs.records()[-1]
    assert last.name == "raises" and last.end >= last.start
    with obs.span("after") as after:
        pass
    assert after.parent is None          # the stack unwound


def test_ring_is_bounded_and_counts_what_it_drops():
    obs.reset()
    extra = 17
    for i in range(obs.CAPACITY + extra):
        with obs.span("fill", step=i):
            pass
    recs = obs.records()
    assert len(recs) == obs.CAPACITY
    assert recs.dropped == extra
    assert recs[0].step == extra          # the oldest went first
    tot = obs.totals()["fill"]
    assert tot["count"] == obs.CAPACITY + extra
    assert tot["max_s"] <= tot["total_s"]
    obs.reset()
    assert len(obs.records()) == 0 and obs.records().dropped == 0
    assert obs.totals() == {}


def test_tally_counts_only_its_own_block():
    with obs.span("before"):
        pass
    with obs.tally() as t:
        for _ in range(3):
            with obs.span("mine") as sp:
                pass
    with obs.span("mine"):
        pass
    got = t.summary()
    assert set(got) == {"mine"} and got["mine"]["count"] == 3
    assert got["mine"]["max_s"] >= sp.seconds


def test_record_lines_up_with_its_annotation_in_a_profile(tmp_path):
    """The record's ``perf_counter`` times, put on the profiler's clock by
    ``to_trace_ns``, fall within 1 ms of the same span's annotation in a
    CPU trace read by ``ProfileData``."""
    from jax.profiler import ProfileData
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("probe.clock", step=5) as sp:
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    start = None
    events = []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            events += [e for e in line.events if e.name == "probe.clock"]
    assert start is not None and len(events) == 1
    ev = events[0]
    assert dict(ev.stats).get("step") == 5
    assert abs(obs.to_trace_ns(sp.start, start) - ev.start_ns) < 1e6
    assert abs(obs.to_trace_ns(sp.end, start) - ev.end_ns) < 1e6


# ---------------------------------------------------------------------------
# the spans train() records
# ---------------------------------------------------------------------------

def _by_id(recs):
    return {r.id: r for r in recs}


def _ancestors(r, by_id):
    out = []
    while r.parent in by_id:
        r = by_id[r.parent]
        out.append(r.name)
    return out


@pytest.fixture(scope="module")
def faulted_run():
    """One smoke run with a parameter flip every 4th step, its runtime
    kept to read its recovery events."""
    import repro.launch.train as T
    real = T.RecoveryRuntime
    runtimes = []

    def keep(*a, **kw):
        rt = real(*a, **kw)
        runtimes.append(rt)
        return rt
    T.RecoveryRuntime = keep
    try:
        first = _next_id()
        out = T.train(get_config("iterpro-100m").smoke(), steps=10,
                      global_batch=2, seq_len=32, inject_every=4,
                      canary_slices=1, donate=True, fused_detect=True,
                      parity=True, verbose=False)
    finally:
        T.RecoveryRuntime = real
    return out, _since(first), runtimes[0]


def test_train_nests_each_fault_downtime(faulted_run):
    out, recs, runtime = faulted_run
    assert out["faults_recovered"] == out["faults_injected"] == 2
    by_id = _by_id(recs)
    faults = [r for r in recs if r.name == "fault"]
    assert [f.step for f in faults] == [4, 8]
    for name, chain in [
            ("snapshot.verify", ["recover.replay", "recover", "fault"]),
            ("snapshot.upload", ["recover.replay", "recover", "fault"]),
            ("canary.refresh", ["fault"]),
            ("parity.rebuild", ["fault"])]:
        got = [r for r in recs if r.name == name]
        assert len(got) == 2, name
        for r in got:
            assert _ancestors(r, by_id) == chain, name
            assert r.step == by_id[r.parent].step
    for r in recs:
        if r.name == "snapshot.upload":
            assert r.attrs["bytes"] > 0
        if r.name == "recover.replay":
            assert r.attrs["steps"] == {4: 4, 8: 0}[r.step]
    assert all(_ancestors(r, by_id) == ["snapshot"]
               for r in recs if r.name in ("snapshot.copy",
                                           "snapshot.digest"))
    assert all(_ancestors(r, by_id) == ["step"]
               for r in recs if r.name in ("step.batch", "step.flag_sync"))
    setup = [r for r in recs if r.name.startswith("setup.")]
    assert {r.name for r in setup} == {"setup.state", "setup.parity",
                                       "setup.compile"}
    assert all(_ancestors(r, by_id) == ["setup"] for r in setup)
    assert all(_ancestors(r, by_id)[-1] == "setup"
               for r in recs if r.name == "compile")
    assert [r.name for r in recs if r.name == "inject"] == ["inject"] * 2


def test_train_reports_its_own_spans(faulted_run):
    out, recs, _ = faulted_run
    spans = out["spans"]
    for name, t in spans.items():
        mine = [r.seconds for r in recs if r.name == name]
        assert t["count"] == len(mine), name
        assert t["total_s"] == pytest.approx(sum(mine), rel=1e-9), name
        assert t["max_s"] == max(mine), name
    # 10 kept steps and the 2 that faulted, each with a batch and a sync
    assert spans["step"]["count"] == spans["step.flag_sync"]["count"] == 12
    assert spans["step.batch"]["count"] == 12


def test_old_timers_read_their_spans(faulted_run):
    out, recs, runtime = faulted_run
    steps = [r.seconds for r in recs if r.name == "step"]
    assert out["mean_step_ms"] == pytest.approx(1e3 * np.mean(steps),
                                                rel=1e-12)
    recovers = [r for r in recs if r.name == "recover"]
    assert len(runtime.events) == len(recovers) == 2
    assert out["mean_recovery_ms"] == pytest.approx(
        1e3 * np.mean([r.seconds for r in recovers]), rel=1e-12)
    for ev, r in zip(runtime.events, recovers):
        assert ev.wall_seconds == r.seconds
        rungs = {x.name[len("recover."):]: x.seconds for x in recs
                 if x.parent == r.id}
        assert ev.phase_seconds == rungs


def test_compile_seconds_sum_the_compile_spans():
    state = {"w": jnp.arange(300, dtype=jnp.float32),
             "iv": {"step": jnp.int32(0)}}
    can = ChecksumCanary(state, n_slices=2)

    def step(t, batch):
        return jax.tree_util.tree_map(lambda x: x + 1, t), \
            {"loss": batch.sum()}
    fac = can.fuse_into_step(step, warm="eager")
    first = _next_id()
    fac.warm(state, jnp.ones((4,)))
    compiles = [r.seconds for r in _since(first) if r.name == "compile"]
    assert fac.n_compiles == len(compiles) == 2
    assert fac.compile_seconds == pytest.approx(sum(compiles), rel=1e-12)


def test_fault_free_step_keeps_one_sync():
    """Spans add no host sync: a fault-free fused step still fetches one
    scalar, whatever the spans around it."""
    import repro.launch.train as T
    cfg = get_config("iterpro-100m").smoke()
    kw = dict(global_batch=2, seq_len=32, canary_slices=1, donate=True,
              fused_detect=True, parity=True, verbose=False,
              snapshot_interval=100)
    counts = []
    for steps in (2, 5):
        dg.STATS.reset()
        T.train(cfg, steps=steps, **kw)
        counts.append(dg.STATS.syncs)
    assert counts[1] - counts[0] == 3
