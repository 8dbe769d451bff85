"""From a profiler trace to the benchmark's device numbers.

``Recorder`` takes a trace of part of a window (``jax.profiler``) and
reads it back.  The reduction works on plain event lists, so that it can
be checked on a small recorded trace:

* device busy time: the union of the intervals in which an operation ran
  on a device (one device's ops may overlap), averaged over the devices;
* per-kernel device time: the durations of the events whose operation is
  a Pallas kernel of that name, with the bytes of its operands and
  results, matched to the kernel call by module and operation name;
* the breakdown: the device operations that took most time, and the
  longest idle gaps, each named by the host span that covers most of it.
"""

from __future__ import annotations

import base64
import bisect
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

#: a device's operation line, and the prefix of the benchmark's spans
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: a TPU op event is named by its HLO instruction: "%fusion.7 = ..."
_OP = re.compile(r"^%?([\w.\-]+) = ")
SPAN = "bench:"


class Event(tuple):
    """(name, start_ns, end_ns, stats)"""
    __slots__ = ()

    def __new__(cls, name, start, end, stats):
        return tuple.__new__(cls, (name, float(start), float(end), stats))

    name = property(lambda s: s[0])
    start = property(lambda s: s[1])
    end = property(lambda s: s[2])
    stats = property(lambda s: s[3])


def load(path: str) -> Dict:
    """Device ops per device (each with the ``hlo_module`` and ``hlo_op``
    it belongs to), and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = defaultdict(list)
    host: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                          for e in lines.get(MODULES_LINE, []))
            starts = [x[0] for x in mods]
            for e in lines.get(OPS_LINE, []):
                stats = dict(e.stats)
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0 and e.start_ns <= mods[i][1]:
                    stats.setdefault("hlo_module", mods[i][2])
                op = _OP.match(e.name)
                if op:
                    stats.setdefault("hlo_op", op.group(1))
                devices[int(m.group(1))].append(Event(
                    e.name, e.start_ns, e.end_ns, stats))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN):
                        host.append(Event(e.name[len(SPAN):], e.start_ns,
                                          e.end_ns, {}))
                        continue
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        cpu_ops.append(Event(e.name, e.start_ns, e.end_ns,
                                             stats))
    if not devices and cpu_ops:
        # the CPU backend runs XLA's operations on host threads
        devices[0] = cpu_ops
    return {"devices": dict(devices), "host": host}


HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4,
                "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(HLO_ITEMSIZE) + r")\[([\d,]*)\]")
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) custom-call\((.*?)\)"
                   r"(.*)$")


def _braced(text: str, key: str) -> Optional[str]:
    """The balanced ``{...}`` that follows ``key`` in ``text``."""
    i = text.find(key)
    if i < 0:
        return None
    i += len(key)
    depth = 0
    for j in range(i, len(text)):
        depth += {"{": 1, "}": -1}.get(text[j], 0)
        if depth == 0:
            return text[i:j + 1]
    return None


def _bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * HLO_ITEMSIZE[dtype]
    return total


def _kernel_text(attrs: str) -> str:
    """A Mosaic call's attributes with its serialized kernel decoded: the
    kernel's name is a string of that body."""
    try:
        cfg, _ = json.JSONDecoder().raw_decode(
            attrs[attrs.index("backend_config=") + len("backend_config="):])
        body = cfg["custom_call_config"]["body"]
        return attrs + base64.b64decode(body).decode("latin-1")
    except (ValueError, KeyError, TypeError):
        return attrs


def hlo_sites(texts: Sequence[str]) -> Dict:
    """Pallas kernel calls of compiled programs (``Compiled.as_text()``):
    ``(module, instruction) -> {"text", "bytes", "result_bytes"}``, the
    bytes of the call's operands and results by their shapes, and the
    call's attributes, which name the kernel."""
    sites = {}
    for text in texts:
        module = None
        for line in text.splitlines():
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                continue
            m = _CALL.match(line)
            if not m or "tpu_custom_call" not in m.group(4):
                continue
            # compiled text names operands without shapes; their layout
            # constraints carry them
            lay = _braced(m.group(4), "operand_layout_constraints=")
            res = _bytes(m.group(2))
            ops = _bytes(lay if lay else m.group(3))
            sites[(module, m.group(1))] = {"text": _kernel_text(m.group(4)),
                                           "bytes": res + ops,
                                           "result_bytes": res}
    return sites


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event]) -> float:
    return sum(e - s for s, e in union([(ev.start, ev.end)
                                        for ev in events]))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of one device inside [lo, hi]."""
    out, t = [], lo
    for s, e in union([(ev.start, ev.end) for ev in events]):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def host_label(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """The innermost benchmark span covering most of the gap ("loop"
    where none does: the program's own loop between the calls the
    benchmark wraps)."""
    best, cover, depth = "loop", 0.0, 0.0
    for ev in host:
        c = min(gap[1], ev.end) - max(gap[0], ev.start)
        if c <= 0:
            continue
        inner = -(ev.end - ev.start)
        if c > cover or (c == cover and inner > depth):
            best, cover, depth = ev.name, c, inner
    return best


def kernel_of(ev: Event, kernels: Sequence[str], site) -> Optional[str]:
    """The Pallas kernel an operation runs: named in the event, in its
    stats, or at its call site in the compiled program."""
    text = ev.name + " " + " ".join(str(v) for v in ev.stats.values())
    if site:
        text += " " + site["text"]
    return next((k for k in kernels if k in text), None)


#: control flow whose events span their bodies' ops: left out of the
#: top operations, so that no time is counted twice there
_CONTAINER = re.compile(r"\) (while|conditional|call)\(|^%?(while|call)[.\d]* ")


def op_label(name: str) -> str:
    """A device op's HLO text without layouts, cut to 160 characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:160]


def site_of(ev: Event, sites: Dict):
    module = ev.stats.get("hlo_module")
    for name in (ev.stats.get("hlo_op"), ev.name):
        if (module, name) in sites:
            return sites[(module, name)]
    return None


def summarize(tr: Dict, sites: Optional[Dict] = None,
              top: int = 10) -> Dict:
    """Busy and window seconds (averaged over devices), the breakdown,
    and the events with their call sites for ``kernel_stats``."""
    devs = tr["devices"]
    out = {"busy_s": 0.0, "window_s": 0.0, "devices": max(1, len(devs)),
           "events": [], "sites": sites or {},
           "breakdown": {"device_ops": [], "idle_gaps": []}}
    if not devs:
        return out
    allev = [ev for evs in devs.values() for ev in evs]
    lo = min(ev.start for ev in allev)
    hi = max(ev.end for ev in allev)
    by_op: Dict[str, float] = defaultdict(float)
    for ev in allev:
        if not _CONTAINER.search(ev.name):
            by_op[op_label(ev.name)] += ev.end - ev.start
    longest = sorted(gaps(devs[min(devs)], lo, hi),
                     key=lambda g: g[0] - g[1])[:top]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    out.update(
        busy_s=sum(busy_ns(evs) for evs in devs.values()) / len(devs)
        * 1e-9,
        window_s=(hi - lo) * 1e-9, events=allev,
        breakdown={
            "device_ops": [[n, t / len(devs) * 1e-9] for n, t in ops],
            "idle_gaps": [[host_label(g, tr["host"]), (g[1] - g[0]) * 1e-9]
                          for g in longest]})
    return out


def kernel_stats(tr: Dict, kernels: Sequence[str]) -> Dict[str, Dict]:
    """Per kernel: device seconds (averaged over devices), calls, and the
    bytes of its calls where every call site is known."""
    n = tr["devices"]
    out: Dict[str, Dict] = {}
    for ev in tr["events"]:
        site = site_of(ev, tr["sites"])
        k = kernel_of(ev, kernels, site)
        if k is None:
            continue
        rec = out.setdefault(k, {"seconds": 0.0, "calls": 0, "bytes": 0.0,
                                 "result_bytes": 0.0, "known_seconds": 0.0})
        secs = (ev.end - ev.start) / n * 1e-9
        rec["seconds"] += secs
        rec["calls"] += 1
        if site is not None:
            rec["known_seconds"] += secs
            rec["bytes"] += site["bytes"] / n
            rec["result_bytes"] += site["result_bytes"] / n
    return out


def roofline_share(run: Dict, kernels: Sequence[str],
                   bytes_rule: str = "operands_and_results"
                   ) -> Optional[float]:
    """Least time the kernels' bytes need at the chip's HBM bandwidth, over
    their device time, for the calls whose call site (and so bytes) is
    known; None where the trace holds no such call."""
    tr = run.get("trace")
    if not tr:
        return None
    recs = list(kernel_stats(tr, kernels).values())
    secs = sum(r["known_seconds"] for r in recs)
    nbytes = sum(r["bytes"] if bytes_rule == "operands_and_results"
                 else 2 * r["result_bytes"] for r in recs)
    if not secs or not nbytes:
        return None
    return 100.0 * nbytes / run["peak"]["hbm_bytes_per_s"] / secs


class Recorder:
    """One profiler trace into a scratch directory."""

    def __init__(self, scratch: str):
        self.dir = os.path.join(scratch, "trace")

    def start(self) -> None:
        import jax
        # no Python tracer: it would slow the host it is meant to watch;
        # the host side is the benchmark's own spans (``spans``)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def path(self) -> str:
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return files[-1]

    def read(self, programs: Sequence = ()) -> Dict:
        """The trace's summary; ``programs``: the compiled programs the
        window ran, whose kernel calls give each kernel's bytes."""
        sites = hlo_sites([p.as_text() for p in programs])
        return summarize(load(self.path()), sites)


class spans:
    """While open, every call of each ``(owner, attribute, name)`` runs
    inside a host span ``bench:<name>`` of the trace: the benchmark's own
    spans around the calls into the program's layers (traced runs only)."""

    def __init__(self, targets):
        self.targets = targets
        self.saved = []

    def __enter__(self):
        import jax
        for owner, attr, name in self.targets:
            real = getattr(owner, attr)

            def wrapped(*a, _real=real, _name=SPAN + name, **kw):
                with jax.profiler.TraceAnnotation(_name):
                    return _real(*a, **kw)
            self.saved.append((owner, attr, real))
            setattr(owner, attr, wrapped)
        return self

    def __exit__(self, *exc):
        for owner, attr, real in reversed(self.saved):
            setattr(owner, attr, real)
        self.saved = []
        return False
