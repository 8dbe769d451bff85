"""The program's own spans (``repro.obs``) in one run's window.

The per-layer readers of the recovery and loop layers read the spans the
program records inside ``train()``: those that lie wholly inside the
run's window, ``[t_window, t_window + window_s]`` on ``perf_counter``, so
that two runs in one process do not mix.  Every function returns None
where there is nothing to read: a program without ``repro.obs``, no such
span in the window, or a window whose oldest spans the ring has dropped.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from bench import trace as btrace

try:
    from repro import obs
except ImportError:          # a program that records no spans
    obs = None


def in_window(run: Dict, names: Sequence[str],
              lo: Optional[float] = None,
              hi: Optional[float] = None) -> Optional[List]:
    """The spans named ``names`` that lie inside ``[lo, hi]`` (default:
    the run's window); None where the spans cannot be read."""
    if obs is None:
        return None
    lo = run["t_window"] if lo is None else lo
    hi = run["t_window"] + run["window_s"] if hi is None else hi
    recs = obs.records()
    if not recs or (recs.dropped and recs[0].start > lo):
        return None
    return [r for r in recs
            if r.name in names and lo <= r.start and r.end <= hi]


def mean_ms(run: Dict, name: str) -> Optional[float]:
    """Mean duration of the window's ``name`` spans, in ms."""
    spans = in_window(run, (name,))
    if not spans:
        return None
    return 1e3 * sum(r.seconds for r in spans) / len(spans)


def per_fault_ms(run: Dict, name: str) -> Optional[float]:
    """Total duration of the window's ``name`` spans per fault recovered
    in the window, in ms."""
    spans = in_window(run, (name,))
    recovered = run["counters"].get("recovered")
    if not spans or not recovered:
        return None
    return 1e3 * sum(r.seconds for r in spans) / recovered


def self_seconds(span, children: Sequence) -> float:
    """A span's duration less what its direct children cover."""
    inner = btrace.union([(c.start, c.end) for c in children
                          if c.parent == span.id])
    return span.seconds - sum(e - s for s, e in inner)


def _executions(events, n):
    """Start and end of each of the ``n`` runs of the program that holds
    most of the device time (the training step): its operations split at
    the ``n - 1`` widest gaps between them.  None where it ran fewer
    operations."""
    by_module = {}
    for ev in events:
        mod = ev.stats.get("hlo_module")
        by_module[mod] = by_module.get(mod, 0.0) + ev.end - ev.start
    step = max(by_module, key=by_module.get)
    ops = sorted((ev.start, ev.end) for ev in events
                 if ev.stats.get("hlo_module") == step)
    if len(ops) < n:
        return None
    reach, gaps = ops[0][1], []
    for i in range(1, len(ops)):
        gaps.append((ops[i][0] - reach, i))
        reach = max(reach, ops[i][1])
    cuts = sorted(i for _, i in sorted(gaps)[len(gaps) - (n - 1):]) \
        if n > 1 else []
    bounds = [0] + cuts + [len(ops)]
    return [(ops[a][0], max(e for _, e in ops[a:b]))
            for a, b in zip(bounds, bounds[1:])]


def trace_origin(run: Dict) -> Optional[float]:
    """The profile's start on the wall clock (ns), found from the
    traced window itself.  Every launch of the training step in it is
    either a step, whose ``step.flag_sync`` ends once that run of the
    step's program has finished, or a step the replay rung replays; in
    time order they pair with the program's runs in the trace.  The
    origin puts the sync that follows its run most closely at that run's
    end: every span comes out earlier than it was by that sync's own lag
    (on one TPU v5e the syncs of a window ended 1.4-2.9 ms after their
    runs)."""
    tr = run.get("trace")
    c = run["counters"]
    if not tr or not tr.get("events") or not c.get("traced_s"):
        return None
    hi = run["t_window"] + c["traced_s"]
    syncs = in_window(run, ("step.flag_sync",), hi=hi)
    if not syncs:
        return None
    launches = sorted([(r.start, r.end) for r in syncs] + [
        (r.start, None) for r in in_window(run, ("recover.replay",), hi=hi)
        for _ in range(r.attrs.get("steps", 0))])
    runs = _executions(tr["events"], len(launches))
    if runs is None:
        return None
    return min(end * 1e9 + obs.WALL_MINUS_PERF_NS - r[1]
               for (_, end), r in zip(launches, runs) if end is not None)
