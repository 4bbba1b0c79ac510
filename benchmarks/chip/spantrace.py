"""The program's own spans in a profiler trace, on the device's clock.

With the observability plane on (``repro.core.obs.configure(True)``),
every stage the engine measures is also a ``jax.profiler.TraceAnnotation``
named ``adaparse.<span>`` on the recording thread's line of the host
plane. ``load`` keeps those events; each event's line is its thread,
named with its index in the plane, since the profiler names the line of
every Python thread ``python``. ``reduce`` takes them, with the device
operations and the ``bench.window_open`` marker that ``devtrace.load``
keeps, over the same window as ``devtrace.reduce`` (the marker,
``seconds`` long), and gives:

- each program span's intervals, clipped to the window, each with its
  thread (seconds from the window's open);
- the device's idle intervals: the window less the union of its
  operations' intervals;
- ``idle_by_span``: each idle gap charged to the innermost program span
  of the consumer thread (the one that waits on the prefetch queue) over
  the gap's middle. Where that span is ``prefetch.wait``, the consumer
  was starved, and the gap goes to the innermost span that the prefetch
  thread (the one that prepares) has open at that moment — what caused
  the wait, such as ``prepare.channel`` or ``gc`` — or stays with
  ``prefetch.wait`` where the prefetch thread has none open. A gap under
  no consumer span is charged to ``untraced``.

A trace without program spans (the plane was off) reduces to None, so a
metric that reads these spans reports nothing.
"""
from __future__ import annotations

import bisect
import dataclasses

import devtrace

PREFIX = "adaparse."
#: the consumer's span while it waits for a prepared batch
STARVED = "prefetch.wait"
#: the stage only the prefetch thread runs
PREPARE = "prepare"
UNTRACED = "untraced"


@dataclasses.dataclass
class Spans:
    window_s: float
    #: span name -> [(start_s, end_s, thread)], clipped to the window
    spans: dict
    #: the device's idle intervals in the window, [(start_s, end_s)]
    idle: list
    #: idle seconds by the span that held the device up
    idle_by_span: dict

    def total_s(self, name: str) -> float:
        """Seconds of span ``name`` inside the window, every thread."""
        return sum(b - a for a, b, _ in self.spans.get(name, []))

    def idle_under_s(self, name: str) -> float:
        """Idle seconds of the device inside spans ``name``."""
        ivs = devtrace._union([(a, b) for a, b, _ in
                               self.spans.get(name, [])])
        return _overlap(self.idle, ivs)


def load(path) -> list:
    """The trace's ``adaparse.*`` host events, as ``devtrace.Event``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name.startswith(devtrace.DEVICE_PLANE):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(devtrace.Event(
                        plane.name, f"{line.name}#{i}", e.name,
                        float(e.start_ns), float(e.duration_ns)))
    return out


def reduce(events: list, seconds: float) -> Spans | None:
    marks = [e for e in events if e.name == devtrace.WINDOW_MARK]
    prog = [e for e in events if e.name.startswith(PREFIX)]
    if not marks or not prog:
        return None
    lo = marks[0].start_ns
    hi = lo + seconds * 1e9
    busy = devtrace._union([(max(e.start_ns, lo), min(e.end_ns, hi))
                            for e in events if devtrace.is_device_op(e)
                            and e.end_ns > lo and e.start_ns < hi])
    idle, t = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > t:
            idle.append((t, a))
        t = max(t, b)

    spans: dict[str, list] = {}
    by_thread: dict[str, list] = {}
    for e in sorted(prog, key=lambda e: (e.start_ns, -e.dur_ns)):
        name = e.name[len(PREFIX):]
        by_thread.setdefault(e.line, []).append((e.start_ns, e.end_ns, name))
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            spans.setdefault(name, []).append(
                ((a - lo) / 1e9, (b - lo) / 1e9, e.line))
    consumer = _thread_of(by_thread, STARVED)
    producer = _thread_of(by_thread, PREPARE, not_=consumer)

    charged: dict[str, float] = {}
    for a, b in idle:
        mid = (a + b) / 2
        label = _innermost(by_thread.get(consumer, []), mid) or UNTRACED
        if label == STARVED:
            label = _innermost(by_thread.get(producer, []), mid) or STARVED
        charged[label] = charged.get(label, 0.0) + (b - a) / 1e9
    return Spans(
        window_s=(hi - lo) / 1e9, spans=spans,
        idle=[((a - lo) / 1e9, (b - lo) / 1e9) for a, b in idle],
        idle_by_span=dict(sorted(charged.items(), key=lambda kv: -kv[1])))


def per_batch_ms(run, seconds_of) -> float | None:
    """``seconds_of(spans)`` over the window's batches, in ms; None
    where the run has no program spans or emitted no batch."""
    spans = getattr(run, "spans", None)
    if spans is None or not run.batches:
        return None
    return 1000.0 * seconds_of(spans) / len(run.batches)


def _thread_of(by_thread: dict, name: str, not_=None):
    """The thread with most spans ``name`` (other than ``not_``)."""
    counts = {t: sum(n == name for _, _, n in ivs)
              for t, ivs in by_thread.items() if t != not_}
    best = max(counts, key=counts.get, default=None)
    return best if best is not None and counts[best] else None


def _innermost(ivs: list, t: float) -> str | None:
    """The name of the latest-starting span of one thread over ``t``
    (a thread's spans nest, so that one is the innermost)."""
    i = bisect.bisect_right(ivs, (t, float("inf"), ""))
    for a, b, name in reversed(ivs[:i]):
        if a <= t < b:
            return name
    return None


def _overlap(xs: list, ys: list) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0.0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total
