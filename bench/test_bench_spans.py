"""The per-layer metrics read from the program's own spans.

Each training cell is rehearsed traced twice in one process on the CPU
(``test_bench_rehearsal``'s smoke cells): every metric that
``BENCHMARK.json`` lists for the cell has to come out as a positive number
both times, computed from the spans of that run's own window.  The arithmetic
of the span readers is checked on made-up spans and device events.
"""

from __future__ import annotations

import types

import pytest

from bench import cells, spans
from bench import run as bench_run
from bench import trace as btrace
from bench.test_bench_rehearsal import rehearse, smoke_root  # noqa: F401

#: the metrics read from inside the program
SPAN_METRICS = {"snapshot_copy_ms.train", "snapshot_digest_ms.train",
                "step_gap_ms.train", "snapshot_verify_ms.train",
                "snapshot_upload_ms.train", "replay_ms_per_step.train",
                "fault_downtime_ms.train"}


@pytest.mark.parametrize("name", ["smoke_train", "smoke_flips"])
def test_span_metrics_twice_in_one_process(smoke_root, name, capsys,  # noqa: F811
                                           monkeypatch):
    spec = cells.load_benchmark(smoke_root)
    want = SPAN_METRICS & {m["name"] for m in
                           cells.metrics_for(spec, name, "per_layer")}
    assert want
    runs = []
    real = bench_run.per_layer

    def keep(spec, name, run, base=None):
        runs.append(run)
        return real(spec, name, run, base)
    monkeypatch.setattr(bench_run, "per_layer", keep)
    lines = [rehearse(smoke_root, name, capsys, trace=1, seed=seed)[0]
             for seed in (2 ** 31 + 21, 2 ** 31 + 22)]
    first, second = runs
    assert first["t_window"] + first["window_s"] < second["t_window"]
    for line, run in zip(lines, runs):
        assert line["correct"], line["checks"]
        got = line["metrics"]
        assert want <= set(got), want - set(got)
        for m in want:
            assert got[m]["value"] > 0, m
        # every span read lies in this run's window
        inside = spans.in_window(run, ("snapshot", "fault"))
        assert inside
        lo, hi = run["t_window"], run["t_window"] + run["window_s"]
        assert all(lo <= r.start and r.end <= hi for r in inside)
        assert len([r for r in inside if r.name == "fault"]) == \
            run["counters"]["faults"]


def _span(i, name, start, end, parent=None, **attrs):
    return types.SimpleNamespace(id=i, name=name, start=start, end=end,
                                 seconds=end - start, parent=parent,
                                 attrs=attrs, step=0)


def _fake(monkeypatch, recs):
    out = spans.obs.Records(recs)
    monkeypatch.setattr(spans.obs, "records", lambda: out)


def _run(events, **counters):
    return {"t_window": 10.0, "window_s": 5.0,
            "trace": {"events": [btrace.Event("op", s, e, {})
                                 for s, e in events]},
            "counters": dict(counters)}


def test_replay_self_time_leaves_out_verify_and_upload(monkeypatch):
    _fake(monkeypatch, [
        _span(2, "snapshot.verify", 11.0, 11.5, parent=1),
        _span(3, "snapshot.upload", 11.5, 11.7, parent=1),
        _span(1, "recover.replay", 11.0, 12.0, steps=2),
        _span(5, "recover.replay", 13.0, 13.1, steps=0),
        _span(6, "recover.replay", 1.0, 9.0, steps=8),     # another run
    ])
    read = cells.metric_reader("replay_ms_per_step.train")
    # (0.3 + 0.1) s of self time over 2 steps
    assert read(_run([])) == pytest.approx(200.0)


def test_step_gap_is_idle_no_covering_span_explains(monkeypatch):
    """Two steps of 1 s with a 10 ms gap, a snapshot of 2 s between the
    second and a third step; the last sync ends 0.1 ms after the last
    operation."""
    t0, ns = 10.0, 1e9
    o = spans.obs.WALL_MINUS_PERF_NS
    origin = (t0 + 0.5) * ns + o                 # the trace's start
    at = lambda t: (t - t0 - 0.5) * ns           # noqa: E731
    events = [(at(10.5), at(11.5)), (at(11.51), at(12.51)),
              (at(14.52), at(15.0))]
    _fake(monkeypatch, [
        _span(1, "step.flag_sync", 11.4, 11.5001),
        _span(2, "step.flag_sync", 12.4, 12.5101),
        _span(3, "snapshot", 12.515, 14.515),
        _span(4, "step.flag_sync", 14.9, 15.0001),
    ])
    run = _run(events, traced_s=5.5, traced_steps=3)
    run["window_s"] = 6.0
    assert spans.trace_origin(run) == pytest.approx(origin + 1e5)
    read = cells.metric_reader("step_gap_ms.train")
    # idle: 10 ms, then 2.01 s of which the snapshot covers 2 s (shifted
    # by the 0.1 ms of the last sync's return)
    assert read(run) == pytest.approx((10.0 + 10.0) / 3, rel=1e-3)


def test_trace_origin_on_the_recorded_v5e_trace(monkeypatch, tmp_path):
    """On the v5e trace of ``danube2l_train``'s steps 4-11, the benchmark's
    own ``step`` spans stand in for the flag syncs (they end just after
    them, on the trace's own clock): the origin found is off by exactly
    the smallest lag of a span's end after its step's run."""
    import gzip
    import os
    import shutil
    path = tmp_path / "t.xplane.pb"
    here = os.path.join(os.path.dirname(__file__), "testdata")
    with gzip.open(os.path.join(here, "danube2l_train.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    tr = btrace.load(str(path))
    steps = [h for h in tr["host"] if h.name == "step"]
    # the run of step 4 began before the trace, its span with it
    events = [ev for ev in tr["devices"][0] if ev.start >= steps[0].start]
    _fake(monkeypatch, [_span(i, "step.flag_sync", h.start * 1e-9,
                              h.end * 1e-9) for i, h in enumerate(steps)])
    run = {"t_window": 0.0, "window_s": 20.0,
           "trace": {"events": events},
           "counters": {"traced_s": 20.0, "traced_steps": len(steps)}}
    runs = spans._executions(events, len(steps))
    assert all(r[1] < h.end < n[0] for r, h, n in
               zip(runs, steps, runs[1:] + [(float("inf"),) * 2]))
    lag = min(h.end - r[1] for h, r in zip(steps, runs))
    assert lag == pytest.approx(2.3004e6, rel=1e-3)
    truth = spans.obs.WALL_MINUS_PERF_NS
    assert spans.trace_origin(run) - truth == pytest.approx(lag, abs=1e3)


def test_readers_give_nothing_without_spans(monkeypatch):
    _fake(monkeypatch, [])
    run = _run([(0.0, 1.0)], traced_s=1.0, traced_steps=1, recovered=1)
    for m in sorted(SPAN_METRICS):
        assert cells.metric_reader(m)(run) is None, m
    monkeypatch.setattr(spans, "obs", None)
    for m in sorted(SPAN_METRICS):
        assert cells.metric_reader(m)(run) is None, m
