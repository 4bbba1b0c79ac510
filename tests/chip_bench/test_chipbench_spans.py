"""The program's spans in a profiler trace (``spantrace``): loading them
with their threads, clipping them to the window, charging each idle gap
of the device to the span that held it up, and the metric readers that
read them, by hand and on a trace recorded on a TPU v5e chip; and
silence where a run has no program spans."""
import json
import threading

import pytest
from chipbench_tiny import BENCH

import devtrace
import harness
import spantrace

READERS = ("prefetch_wait_ms", "prepare_host_ms", "prepare_wait_ms",
           "route_wait_ms", "gc_ms", "starved_frac")


def _by_hand():
    ev = devtrace.Event
    dev, host, ops = "/device:TPU:0", "/host:CPU", devtrace.OPS_LINE
    main, pf = "python#0", "python#1"

    def prog(line, name, a, b):
        return ev(host, line, "adaparse." + name, float(a), float(b - a))

    return [
        ev(host, "python", devtrace.WINDOW_MARK, 1_000.0, 0.0),
        ev(dev, ops, "%fusion.1 = f32[8] fusion(...)", 1_500.0, 1_500.0),
        ev(dev, ops, "%fusion.2 = f32[8] fusion(...)", 6_000.0, 1_500.0),
        ev(dev, ops, "%fusion.3 = f32[8] fusion(...)", 9_600.0, 900.0),
        # the consumer: waits (from before the window), routes, waits
        # again, reparses, then nothing to the window's end
        prog(main, "prefetch.wait", 500, 2_000),
        prog(main, "route", 2_000, 5_000),
        prog(main, "route.wait", 2_500, 4_800),
        prog(main, "prefetch.wait", 5_000, 9_500),
        prog(main, "reparse", 9_500, 10_000),
        # the prefetch thread: two prepares, the second past the window
        # with a collection inside its cheap channel
        prog(pf, "prepare", 0, 1_800),
        prog(pf, "prepare.channel", 0, 1_200),
        prog(pf, "prepare.wait", 1_300, 1_800),
        prog(pf, "prepare", 6_500, 12_000),
        prog(pf, "prepare.channel", 6_500, 9_000),
        prog(pf, "gc", 7_000, 8_800),
        prog(pf, "prepare.wait", 9_000, 12_000),
    ]


def _run(spans, n_batches=2):
    run = harness.Run({}, {}, [{}] * n_batches, [], (0.0, 1.0), 0, None,
                      None)
    run.spans = spans
    return run


def test_spans_clipped_and_idle_charged_by_hand():
    got = spantrace.reduce(_by_hand(), 10e-6)
    assert got.window_s == pytest.approx(10e-6)
    # busy 1500-3000, 6000-7500, 9600-10500 of the window 1000-11000
    assert got.idle == pytest.approx([(0.0, 0.5e-6), (2e-6, 5e-6),
                                      (6.5e-6, 8.6e-6), (9.5e-6, 10e-6)])
    # each span clipped to the window, with its thread
    assert got.spans["prefetch.wait"] == [
        (0.0, pytest.approx(1e-6), "python#0"),
        (pytest.approx(4e-6), pytest.approx(8.5e-6), "python#0")]
    assert got.spans["prepare"][1] == (pytest.approx(5.5e-6),
                                       pytest.approx(10e-6), "python#1")
    assert got.total_s("prepare") == pytest.approx(5.3e-6)
    assert got.total_s("prepare.wait") == pytest.approx(2.5e-6)
    # gaps, by the middle: 1250 under the consumer's wait, while the
    # prefetch thread is between its channel and its wait (prepare);
    # 4500 under route.wait; 8550 under the wait, while the prefetch
    # thread collects (gc); 10750 under no consumer span
    assert got.idle_by_span == {
        "route.wait": pytest.approx(3e-6), "gc": pytest.approx(2.1e-6),
        "prepare": pytest.approx(0.5e-6), "untraced": pytest.approx(0.5e-6)}
    assert sum(got.idle_by_span.values()) == pytest.approx(6.1e-6)
    # the device idle inside the consumer's waits: 1000-1500,
    # 5000-6000 (a gap charged to route.wait by its middle) and
    # 7500-9500
    assert got.idle_under_s("prefetch.wait") == pytest.approx(3.5e-6)


def test_metric_readers_by_hand():
    run = _run(spantrace.reduce(_by_hand(), 10e-6))
    read = {m: harness.metric_reader(m)(run) for m in READERS}
    assert read == {
        "prefetch_wait_ms": pytest.approx(1e3 * 5.5e-6 / 2),
        "prepare_host_ms": pytest.approx(1e3 * (5.3e-6 - 2.5e-6) / 2),
        "prepare_wait_ms": pytest.approx(1e3 * 2.5e-6 / 2),
        "route_wait_ms": pytest.approx(1e3 * 2.3e-6 / 2),
        "gc_ms": pytest.approx(1e3 * 1.8e-6 / 2),
        "starved_frac": pytest.approx(0.35)}


def test_readers_are_silent_without_program_spans():
    """A run whose trace holds no program span (the plane off, or a
    program without the spans) reduces to None and reads nothing,
    as does a harness run that never looked for them."""
    no_prog = [e for e in _by_hand() if not e.name.startswith("adaparse.")]
    assert spantrace.reduce(no_prog, 10e-6) is None
    for run in (_run(None),
                harness.Run({}, {}, [{}], [], (0.0, 1.0), 0, None, None)):
        assert all(harness.metric_reader(m)(run) is None for m in READERS)


def test_load_keeps_program_spans_with_their_threads(tmp_path):
    """On the CPU's profiler: the program's spans land on each thread's
    own line, as ``adaparse.<name>``, with the children inside."""
    import jax

    from repro.core import obs

    def prepare():
        with obs.span("prepare", 1), obs.span("prepare.wait", 1):
            pass

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    obs.configure(True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t = threading.Thread(target=prepare)
        t.start()
        t.join(timeout=30)
        with obs.span("route", 0), obs.span("route.wait", 0):
            pass
    finally:
        jax.profiler.stop_trace()
        obs.configure(False)
    assert not t.is_alive()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = spantrace.load(path)
    lines = {e.name: e.line for e in events}
    assert set(lines) == {"adaparse.prepare", "adaparse.prepare.wait",
                          "adaparse.route", "adaparse.route.wait"}
    assert lines["adaparse.prepare"] == lines["adaparse.prepare.wait"] \
        != lines["adaparse.route"] == lines["adaparse.route.wait"]
    by = {e.name: e for e in events}
    assert by["adaparse.route"].start_ns <= by["adaparse.route.wait"].start_ns
    assert by["adaparse.route.wait"].end_ns <= by["adaparse.route"].end_ns


def test_reduction_on_a_recorded_trace_with_program_spans():
    rec = json.loads((BENCH / "testdata" / "trace_excerpt_spans.json")
                     .read_text())
    events = [devtrace.Event(*e[:5], tuple(map(tuple, e[5])))
              for e in rec["events"]]
    got = spantrace.reduce(events, rec["seconds"])
    want = rec["expected"]
    assert got.window_s == pytest.approx(rec["seconds"])
    assert got.idle_by_span == pytest.approx(want["idle_by_span"],
                                             rel=1e-9)
    assert {n: got.total_s(n) for n in got.spans} == pytest.approx(
        want["span_s"], rel=1e-9)
    assert got.idle_under_s("prefetch.wait") == pytest.approx(
        want["idle_under_prefetch_wait_s"], rel=1e-9)
    # the consumer's and the prefetch thread's lines told apart: the
    # route step's wait and the prefetch thread's prepare on two threads
    threads = {n: {t for _, _, t in ivs} for n, ivs in got.spans.items()}
    assert threads["route.wait"] == threads["prefetch.wait"] \
        != threads["prepare"] == threads["prepare.wait"]
    # every idle second of the device reduction of the same events is
    # charged to some span
    dev = devtrace.reduce(events, rec["seconds"])
    assert dev.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert sum(got.idle_by_span.values()) == pytest.approx(
        got.window_s - dev.busy_s, rel=1e-9)
    run = _run(got, n_batches=2)
    read = {m: harness.metric_reader(m)(run) for m in READERS}
    assert read["route_wait_ms"] == pytest.approx(
        1e3 * want["span_s"]["route.wait"] / 2)
    assert read["prepare_host_ms"] + read["prepare_wait_ms"] == \
        pytest.approx(1e3 * want["span_s"]["prepare"] / 2)
    assert read["gc_ms"] == 0.0
    assert read["starved_frac"] == pytest.approx(
        want["idle_under_prefetch_wait_s"] / rec["seconds"])
