"""Reduce one profiler trace of a run's window to device numbers.

``load`` flattens the ``.xplane.pb`` the JAX profiler writes into plain
events (plane, line, name, start and duration in ns, stats), so the
reduction below is checked on a small recorded trace without JAX.
``reduce`` then takes, within the traced window (from the run's
``bench.window_open`` marker, ``seconds`` long):

- busy: the union of the intervals of the device's operations;
- each device operation's seconds and count, by its name without the
  ``.N`` suffix (a Pallas kernel's custom-call is named after its jitted
  wrapper: ``fast_features_kernel.1`` -> ``fast_features_kernel``), so a
  metric reader finds its kernel by name;
- the device operations that took most time, each named
  ``<jitted program>/<operation>`` (the trace names an operation by
  its HLO text, ``%fusion.149 = ...``, and its program by the module
  events it runs inside);
- the idle gaps, each charged to what the host was doing in it: the
  innermost ``bench.*`` span of the consumer thread that covers the
  gap's middle (route, complete, probe), else of the prefetch thread
  (prepare), else ``host`` outside any stage.
"""
from __future__ import annotations

import bisect
import dataclasses

WINDOW_MARK = "bench.window_open"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations that only hold others (a scan's loop): their time is
#: their body's, so the list of top operations leaves them out
CONTAINERS = ("while", "conditional", "call")
STAGE_PREFIX = "bench."
#: the consumer's stages come before the prefetch thread's when a gap
#: lies under both
STAGE_ORDER = ("bench.route", "bench.complete", "bench.probe",
               "bench.prepare")
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """``fusion.149`` of an operation, ``route_step`` of a module."""
        if self.line == MODULES_LINE:
            return self.name.split("(")[0].removeprefix("jit_")
        return self.name.split(" = ")[0].lstrip("%")

    @property
    def root(self) -> str:
        """An operation's name without its ``.N`` suffix."""
        return self.short.split(".")[0]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops: dict
    breakdown: dict


def load(path) -> list[Event]:
    """The trace's device operations and the run's ``bench.*`` spans."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(STAGE_PREFIX):
                    out.append(Event(
                        plane.name, line.name, e.name, float(e.start_ns),
                        float(e.duration_ns),
                        tuple((k, str(v)) for k, v in e.stats)))
    return out


def is_device_op(e: Event) -> bool:
    return e.plane.startswith(DEVICE_PLANE) and e.line == OPS_LINE


def reduce(events: list[Event], seconds: float) -> Reduced | None:
    marks = [e for e in events if e.name == WINDOW_MARK]
    if not marks:
        return None
    lo = marks[0].start_ns
    hi = lo + seconds * 1e9
    ops = [e for e in events if is_device_op(e)
           and e.end_ns > lo and e.start_ns < hi]
    if not ops:
        return None
    busy = _union([(max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops])
    by_name: dict[str, tuple[float, int]] = {}
    for e in ops:
        s, n = by_name.get(e.root, (0.0, 0))
        by_name[e.root] = (s + e.dur_ns / 1e9, n + 1)
    modules = sorted((e.start_ns, e.end_ns, e.short) for e in events
                     if e.plane.startswith(DEVICE_PLANE)
                     and e.line == MODULES_LINE)
    per_op: dict[str, float] = {}
    for e in ops:
        if e.root in CONTAINERS:
            continue
        name = f"{_module(modules, e.start_ns)}/{e.short}"
        per_op[name] = per_op.get(name, 0.0) + e.dur_ns / 1e9
    spans: dict[str, list] = {}
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.name in STAGE_ORDER:
            spans.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    idle: dict[str, float] = {}
    t = lo
    for a, b in busy + [(hi, hi)]:
        if a > t:
            label = _label(spans, (t + a) / 2)
            idle[label] = idle.get(label, 0.0) + (a - t) / 1e9
        t = max(t, b)
    busy_s = sum(b - a for a, b in busy) / 1e9
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=busy_s, ops=by_name,
        breakdown={"device_ops": _top(per_op), "idle_gaps": _top(idle)})


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _module(modules: list, t: float) -> str:
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else "?"


def _label(spans: dict, t: float) -> str:
    """The first stage in ``STAGE_ORDER`` with a span over ``t`` (the
    spans of one stage never overlap: one thread runs each stage)."""
    for name in STAGE_ORDER:
        ivs = spans.get(name, [])
        i = bisect.bisect_right(ivs, (t, float("inf"))) - 1
        if i >= 0 and t < ivs[i][1]:
            return name[len(STAGE_PREFIX):]
    return "host"


def _top(d: dict) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:TOP]
