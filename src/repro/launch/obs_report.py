"""Summarize a run's trace directory (``serve.py --trace-dir``).

    PYTHONPATH=src python -m repro.launch.obs_report --trace-dir DIR

Replays ``spans.jsonl`` (the span log ``core/obs.TraceWriter`` wrote)
and prints:

- a per-stage latency table — count, p50/p95/p99 (exact percentiles
  over the recorded durations, not histogram-bucket estimates), total
  seconds and total self seconds per span stage (a span's self time is
  its duration minus what its child spans on the same thread cover);
  the waits and pauses (``prepare.wait``, ``route.wait``,
  ``prefetch.wait``, ``gc``, ``compile``) in a table of their own;
- a per-worker table — span count, busy seconds (the self time of the
  work stages, so a stage's device wait or a collection inside it is
  not counted as work), busy fraction of the trace window, seconds
  waited or paused, and the stages seen on that lane;
- the re-issue cause breakdown (``crash`` / ``wedged`` / ``stalled``,
  parsed from the coordinator's ``reissue`` span details) and the
  dedup / cache-hit counts the span-conservation laws guarantee;
- fabric membership (``join`` / ``leave`` / ``admission_rejected``
  lifecycle spans) when the run used the cross-machine fabric runtime —
  remote workers show up as ordinary per-worker lanes, keyed by the
  node id the coordinator assigned at admission.

It also (re)generates the Chrome ``trace_event`` artifact from the
span log — ``--chrome-out FILE`` writes it elsewhere (default: refresh
``trace.json`` inside the trace dir), so a spans.jsonl shipped without
its sibling is still loadable in chrome://tracing or Perfetto.
"""
from __future__ import annotations

import argparse
import math
import os
import shutil
from collections import Counter, defaultdict

from repro.core import obs

#: Stages whose self time is real work on a worker lane. ``complete``
#: is excluded: the coordinator attributes it to the winning worker
#: with the full batch wall, which already contains the stage spans.
WORK_STAGES = ("prepare", "prepare.channel", "prepare.features", "route",
               "reparse", "probe", "cache_lookup")
#: Spans that are a thread blocked or paused, never work
WAIT_STAGES = ("prepare.wait", "route.wait", "prefetch.wait", "gc",
               "compile")


def _pct(vals: list, q: float) -> float:
    """Nearest-rank percentile over the raw measured durations."""
    if not vals:
        return 0.0
    rank = max(math.ceil(q * len(vals)), 1)
    return sorted(vals)[rank - 1]


def _lane(node: int) -> str:
    return "coordinator" if node < 0 else f"worker {node}"


def self_times(spans) -> list:
    """Each span's duration minus the durations of its children: the
    spans recorded on the same thread of the same process, inside it,
    that name it as their parent. Spans without a thread (logs written
    before spans had one) keep their whole duration."""
    out = [s.dur for s in spans]
    lanes: dict[tuple, list] = defaultdict(list)
    for i, s in enumerate(spans):
        if s.thread and s.dur > 0:
            lanes[s.pid, s.thread].append(i)
    for idx in lanes.values():
        idx.sort(key=lambda i: (spans[i].start, -spans[i].dur))
        open_: list = []                 # enclosing spans, innermost last
        for i in idx:
            s = spans[i]
            while open_ and (spans[open_[-1]].start + spans[open_[-1]].dur
                             <= s.start):
                open_.pop()
            for j in reversed(open_):
                if (spans[j].name == s.parent
                        and spans[j].start + spans[j].dur > s.start):
                    out[j] -= s.dur
                    break
            open_.append(i)
    return out


def summarize(spans, meta: dict | None = None) -> dict:
    """The report as a plain dict (the CLI renders it; tests assert
    on it)."""
    meta = meta or {}
    starts = [s.start for s in spans]
    ends = [s.start + s.dur for s in spans]
    window = (max(ends) - min(starts)) if spans else 0.0

    by_stage: dict[str, list] = defaultdict(list)
    self_s: Counter = Counter()
    by_worker: dict[int, dict] = defaultdict(
        lambda: {"spans": 0, "busy_s": 0.0, "wait_s": 0.0,
                 "stages": Counter()})
    causes: Counter = Counter()
    n_complete = n_dedup = n_cached = 0
    fabric: Counter = Counter()
    for s, own in zip(spans, self_times(spans)):
        by_stage[s.name].append(s.dur)
        self_s[s.name] += own
        w = by_worker[s.node]
        w["spans"] += 1
        w["stages"][s.name] += 1
        if s.name in WORK_STAGES:
            w["busy_s"] += own
        elif s.name in WAIT_STAGES:
            w["wait_s"] += own
        if s.name == "reissue":
            causes[s.detail.split(" ", 1)[0] or "unknown"] += 1
        elif s.name == "complete":
            n_complete += 1
            n_cached += bool(s.cached)
        elif s.name == "dedup":
            n_dedup += 1
        elif s.name in ("join", "leave", "admission_rejected"):
            fabric[s.name] += 1

    stages = {
        name: {"n": len(durs), "p50_s": _pct(durs, 0.50),
               "p95_s": _pct(durs, 0.95), "p99_s": _pct(durs, 0.99),
               "total_s": sum(durs), "self_s": self_s[name]}
        for name, durs in by_stage.items()}
    workers = {
        node: {"spans": w["spans"], "busy_s": w["busy_s"],
               "busy_frac": (w["busy_s"] / window) if window else 0.0,
               "wait_s": w["wait_s"], "stages": dict(w["stages"])}
        for node, w in by_worker.items()}
    return {"n_spans": len(spans), "dropped": meta.get("dropped", 0),
            "window_s": window, "stages": stages, "workers": workers,
            "reissue_causes": dict(causes), "complete": n_complete,
            "complete_cached": n_cached, "dedup": n_dedup,
            "fabric": {"joins": fabric["join"], "leaves": fabric["leave"],
                       "rejected": fabric["admission_rejected"]}}


def render(rep: dict) -> str:
    out = [f"[obs] {rep['n_spans']} spans over {rep['window_s']:.2f} s "
           f"({rep['dropped']} dropped at the ring)"]
    order = {n: i for i, n in enumerate(obs.SPAN_STAGES)}
    names = sorted(rep["stages"], key=lambda n: order.get(n, 99))
    for title, rows in (("stage", [n for n in names
                                   if n not in WAIT_STAGES]),
                        ("wait or pause", [n for n in names
                                           if n in WAIT_STAGES])):
        if not rows:
            continue
        out.append(f"{title:<18}{'n':>6}{'p50 ms':>10}{'p95 ms':>10}"
                   f"{'p99 ms':>10}{'total s':>10}{'self s':>10}")
        for name in rows:
            st = rep["stages"][name]
            out.append(f"{name:<18}{st['n']:>6}{st['p50_s'] * 1e3:>10.2f}"
                       f"{st['p95_s'] * 1e3:>10.2f}"
                       f"{st['p99_s'] * 1e3:>10.2f}"
                       f"{st['total_s']:>10.2f}{st['self_s']:>10.2f}")
        out.append("")
    out.append(f"{'lane':<14}{'spans':>6}{'busy s':>10}{'busy %':>8}"
               f"{'waited s':>10}  stages")
    for node in sorted(rep["workers"]):
        w = rep["workers"][node]
        seen = ",".join(sorted(w["stages"]))
        out.append(f"{_lane(node):<14}{w['spans']:>6}{w['busy_s']:>10.2f}"
                   f"{w['busy_frac'] * 100:>7.1f}%{w['wait_s']:>10.2f}"
                   f"  {seen}")
    out.append("")
    causes = rep["reissue_causes"]
    cause_s = (", ".join(f"{c} {n}" for c, n in sorted(causes.items()))
               if causes else "none")
    out.append(f"re-issues: {cause_s}")
    out.append(f"completes: {rep['complete']} "
               f"({rep['complete_cached']} cached)  "
               f"dedup drops: {rep['dedup']}")
    fab = rep.get("fabric") or {}
    if any(fab.values()):
        out.append(f"fabric membership: {fab['joins']} joined, "
                   f"{fab['leaves']} left, {fab['rejected']} rejected "
                   f"(live delta {fab['joins'] - fab['leaves']:+d})")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="summarize an adaparse trace directory")
    ap.add_argument("--trace-dir", required=True, metavar="DIR",
                    help="directory serve.py --trace-dir wrote "
                         "(needs spans.jsonl)")
    ap.add_argument("--chrome-out", default=None, metavar="FILE",
                    help="where to write the regenerated Chrome "
                         "trace_event JSON (default: trace.json inside "
                         "the trace dir)")
    args = ap.parse_args(argv)
    try:
        spans, meta = obs.load_spans(args.trace_dir)
    except FileNotFoundError:
        ap.error(f"no spans.jsonl under {args.trace_dir!r}; run "
                 f"serve.py with --trace-dir first")
    rep = summarize(spans, meta)
    print(render(rep))
    chrome = obs.TraceWriter(args.trace_dir).write(
        spans, dropped=meta.get("dropped", 0))
    if args.chrome_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.chrome_out)),
                    exist_ok=True)
        shutil.copyfile(chrome, args.chrome_out)
        chrome = args.chrome_out
    print(f"\nChrome trace: {chrome} (open in chrome://tracing or "
          f"https://ui.perfetto.dev)")
    return rep


if __name__ == "__main__":
    main()
