"""The observability plane (core/obs): recorder noop contract, exact
log2-histogram folding, Prometheus text rendering, trace-writer
round-trips, the engine's measured stage spans and the plane's
garbage-collector and compile hooks — and the span-conservation
property on a real 4-worker crash/flap process campaign: every emitted
batch has exactly one winning ``complete`` span, every dropped
duplicate a ``dedup`` span, every re-issue a ``reissue`` span, and the
trace-file replay counts match the ``ExecutorResult`` counters
exactly."""
import gc
import json
from collections import Counter

import numpy as np
import pytest

from repro.core import obs
from repro.core.campaign import (CampaignExecutor, ExecutorConfig,
                                 FaultInjection)
from repro.core.engine import EngineConfig


# ---------------------------------------------------------------------------
# Recorder
# ---------------------------------------------------------------------------


def test_recorder_is_noop_by_default():
    rec = obs.recorder()
    assert not rec.enabled
    # recording through the noop is free of state: nothing to drain
    rec.span("prepare", 0, 0.0, 1.0)
    assert rec.drain() == []
    assert rec.dropped == 0


def test_ring_recorder_records_and_drains_in_order():
    rec = obs.RingRecorder(cap=64, node=3)
    for k in range(5):
        rec.span("prepare", k, float(k), 0.5)
    got = rec.drain()
    assert [s.trace for s in got] == [str(k) for k in range(5)]
    assert all(s.node == 3 for s in got)
    assert rec.drain() == []             # drained empty
    assert rec.dropped == 0


def test_ring_recorder_overflow_is_drop_counted_never_blocking():
    rec = obs.RingRecorder(cap=4, node=0)
    for k in range(10):
        rec.span("route", k, float(k), 0.1)
    got = rec.drain()
    assert len(got) == 4                 # bounded ring kept the newest
    assert rec.dropped == 6
    assert [s.trace for s in got] == ["6", "7", "8", "9"]


def test_configure_swaps_recorder_and_restores_noop():
    rec = obs.configure(enabled=True, cap=16, node=1)
    try:
        assert rec.enabled and obs.recorder() is rec
    finally:
        rec2 = obs.configure(enabled=False)
    assert not rec2.enabled and obs.recorder() is rec2


# ---------------------------------------------------------------------------
# Metrics: histograms fold exactly across processes
# ---------------------------------------------------------------------------


def test_histogram_buckets_are_log2_and_merge_exactly():
    a, b = obs.Registry(), obs.Registry()
    vals_a = [1e-6, 3e-4, 0.01, 0.8, 2.5]
    vals_b = [2e-5, 0.01, 0.01, 7.0]
    for v in vals_a:
        a.observe("lat", v)
    for v in vals_b:
        b.observe("lat", v)
    both = obs.Registry()
    for v in vals_a + vals_b:
        both.observe("lat", v)
    folded = obs.fold([a.snapshot(), b.snapshot()])
    # elementwise-exact: the fold of two processes' buckets equals one
    # process having observed every value
    assert folded["hists"]["lat"] == both.snapshot()["hists"]["lat"]
    assert folded["hists"]["lat"]["total"] == len(vals_a) + len(vals_b)


def test_histogram_quantiles_bracket_observations():
    r = obs.Registry()
    for v in [0.001] * 90 + [1.0] * 10:
        r.observe("lat", v)
    h = r.hists["lat"]
    assert h.quantile(0.5) == pytest.approx(0.001, rel=1.0)
    assert h.quantile(0.99) == pytest.approx(1.0, rel=1.0)


def test_fold_counters_add_and_diff_subtracts_baseline():
    a, b = obs.Registry(), obs.Registry()
    a.count("pool.batches_done", 3)
    b.count("pool.batches_done", 4)
    b.gauge("worker.queue_depth.n1", 2)
    folded = obs.fold([a.snapshot(), b.snapshot()])
    assert folded["counters"]["pool.batches_done"] == 7
    assert folded["gauges"]["worker.queue_depth.n1"] == 2
    base = a.snapshot()
    a.count("pool.batches_done", 5)
    a.observe("lat", 0.1)
    d = obs.diff(a.snapshot(), base)
    assert d["counters"] == {"pool.batches_done": 5}
    assert d["hists"]["lat"]["total"] == 1


def test_prometheus_text_renders_all_metric_kinds():
    r = obs.Registry()
    r.count("pool.reissued", 2)
    r.gauge("pool.window", 3)
    r.observe("engine.route_s", 0.01)
    text = obs.prometheus_text(obs.fold([r.snapshot()]))
    assert "# TYPE adaparse_pool_reissued counter" in text
    assert "adaparse_pool_reissued_total 2" in text
    assert "adaparse_pool_window 3" in text
    assert 'adaparse_engine_route_s_bucket{le="+Inf"} 1' in text
    assert "adaparse_engine_route_s_count 1" in text


# ---------------------------------------------------------------------------
# Trace writer
# ---------------------------------------------------------------------------


def _some_spans():
    return [
        obs.Span("prepare", "7", 0, 4242, 100.0, 0.5),
        obs.Span("complete", "7", 1, 4243, 100.6, 1.2, attempt=1,
                 cached=True),
        obs.Span("dedup", "7", 2, 4244, 101.9, 0.0, abandoned=True,
                 detail="lost completion race"),
    ]


def test_trace_writer_roundtrip_and_chrome_json(tmp_path):
    # a prefetch thread's prepare overlaps the main thread's route on
    # worker 0: each thread gets a lane of its own
    spans = _some_spans() + [
        obs.Span("route", "8", 0, 4242, 100.2, 0.4, parent="",
                 thread="MainThread"),
        obs.Span("prepare", "8", 0, 4242, 100.1, 0.6, thread="prefetch")]
    chrome = obs.TraceWriter(tmp_path).write(spans, dropped=2)
    got, meta = obs.load_spans(tmp_path)
    assert got == spans                  # lossless jsonl round-trip
    assert meta == {"n_spans": 5, "dropped": 2}
    doc = json.loads(chrome.read_text())
    events = doc["traceEvents"]
    lanes = {e["tid"]: e["args"]["name"] for e in events
             if e.get("name") == "thread_name"}
    assert set(lanes.values()) == {"worker 0", "worker 1", "worker 2",
                                   "worker 0 prefetch"}
    by_name = {(e["name"], e["args"]["trace"]): lanes[e["tid"]]
               for e in events if e.get("ph") in ("X", "i")}
    assert by_name["route", "8"] == "worker 0"
    assert by_name["prepare", "8"] == "worker 0 prefetch"
    durs = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    assert len(durs) == 4 and len(instants) == 1
    assert all(e["ts"] >= 100.0 * 1e6 for e in durs)
    # span logs written before spans had a parent and a thread load
    old = tmp_path / "old"
    old.mkdir()
    (old / "spans.jsonl").write_text(json.dumps(
        {k: v for k, v in spans[0].to_dict().items()
         if k not in ("parent", "thread")}) + "\n")
    assert obs.load_spans(old)[0] == [spans[0]]


def test_obs_report_summarizes_stages_workers_and_causes(tmp_path):
    from repro.launch import obs_report

    spans = _some_spans() + [
        obs.Span("reissue", "8", 0, 4242, 102.0, 0.0,
                 detail="crash worker 2, prepare stage"),
        obs.Span("reissue", "9", 1, 4242, 102.5, 0.0,
                 detail="wedged worker 0, complete stage"),
    ]
    # one measured batch on worker 0: the prefetch thread prepares
    # (a collection inside its cheap channel, then a device wait), the
    # main thread waits on the queue, then routes and waits on the step
    pf, main = dict(thread="prefetch"), dict(thread="MainThread")
    spans += [
        obs.Span("prepare", "10", 0, 4242, 103.0, 0.30, **pf),
        obs.Span("prepare.channel", "10", 0, 4242, 103.0, 0.05,
                 parent="prepare", **pf),
        obs.Span("gc", "10", 0, 4242, 103.01, 0.02,
                 parent="prepare.channel", detail="7 collected", **pf),
        obs.Span("prepare.wait", "10", 0, 4242, 103.1, 0.20,
                 parent="prepare", **pf),
        obs.Span("prefetch.wait", "10", 0, 4242, 103.0, 0.31, **main),
        obs.Span("route", "10", 0, 4242, 103.31, 0.25, **main),
        obs.Span("route.wait", "10", 0, 4242, 103.32, 0.20,
                 parent="route", **main),
    ]
    obs.TraceWriter(tmp_path).write(spans)
    rep = obs_report.main(["--trace-dir", str(tmp_path)])
    assert rep["n_spans"] == 12
    assert rep["stages"]["prepare"]["n"] == 2
    assert rep["stages"]["prepare"]["p50_s"] == pytest.approx(0.3)
    assert rep["reissue_causes"] == {"crash": 1, "wedged": 1}
    assert rep["complete"] == 1 and rep["complete_cached"] == 1
    assert rep["dedup"] == 1
    # self time: a span less its children on its own thread (the old
    # threadless prepare keeps its whole 0.5 s)
    own = {k: v["self_s"] for k, v in rep["stages"].items()}
    assert own["prepare"] == pytest.approx(0.5 + 0.30 - 0.05 - 0.20)
    assert own["prepare.channel"] == pytest.approx(0.03)
    assert own["route"] == pytest.approx(0.05)
    assert own["prefetch.wait"] == pytest.approx(0.31)
    # work is self time of the work stages; waits and pauses are apart
    w0 = rep["workers"][0]
    assert w0["busy_s"] == pytest.approx(0.55 + 0.03 + 0.05)
    assert w0["wait_s"] == pytest.approx(0.20 + 0.02 + 0.31 + 0.20)
    text = obs_report.render(rep)
    assert "crash 1" in text and "wedged 1" in text
    waits = text[text.index("wait or pause"):text.index("lane")]
    assert all(n in waits for n in ("prefetch.wait", "gc", "route.wait"))
    assert "reparse" not in waits and "\nroute " not in waits


# ---------------------------------------------------------------------------
# Measured spans inside the engine, and the plane's hooks
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_llm(corpus):
    """A one-layer float32 llm router over the shared corpus's vocab,
    routed on the device path with the fast_features kernel forced
    (interpret mode here), and 3 batches of 16 documents."""
    from repro.common import unwrap
    from repro.configs.base import EncoderConfig
    from repro.core.router import AdaParseRouter, LinearStage
    from repro.models import encoder as enc_lib

    ccfg, docs = corpus
    cfg = EncoderConfig(name="t", n_layers=1, d_model=16, n_heads=2,
                        d_ff=32, vocab_size=ccfg.vocab_size, max_len=12,
                        param_dtype="float32", compute_dtype="float32")
    router = AdaParseRouter("llm", LinearStage(np.zeros(8), 1.0), None,
                            enc_cfg=cfg,
                            enc_params=unwrap(enc_lib.init_encoder(cfg, 0)))
    ecfg = EngineConfig(alpha=0.1, batch_size=16, prefetch_depth=1,
                        feature_kernel="force")
    return ecfg, router, ccfg, docs[:48]


def _run_tiny(tiny_llm, enabled: bool):
    from repro.core.engine import AdaParseEngine

    ecfg, router, ccfg, docs = tiny_llm
    rec = obs.configure(enabled)
    base = obs.metrics().snapshot()
    try:
        out = AdaParseEngine(ecfg, router, ccfg).run(docs)
        spans = rec.drain()
    finally:
        obs.configure(False)
    assert len(out) == len(docs)
    return spans, obs.diff(obs.metrics().snapshot(), base)


def test_engine_spans_are_measured_where_the_work_happens(tiny_llm):
    spans, metrics = _run_tiny(tiny_llm, True)
    assert {s.name for s in spans} <= set(obs.SPAN_STAGES)
    stages = [s for s in spans if s.name not in ("gc", "compile")]
    by = Counter((s.trace, s.name) for s in stages)
    keys = ["0", "1", "2"]
    children = {"prepare": ("prepare.channel", "prepare.features",
                            "prepare.wait"),
                "route": ("route.wait",)}
    for k in keys:
        for parent, kids in children.items():
            assert by[k, parent] == 1
            (p,) = [s for s in stages if (s.trace, s.name) == (k, parent)]
            for kid in kids:
                assert by[k, kid] == 1
                (c,) = [s for s in stages if (s.trace, s.name) == (k, kid)]
                assert (c.parent, c.thread) == (parent, p.thread)
                # the child lies inside its parent (two clocks: start on
                # time.time, duration on perf_counter)
                assert c.start >= p.start - 1e-4
                assert c.start + c.dur <= p.start + p.dur + 1e-4
        assert by[k, "reparse"] == 1 and by[k, "prefetch.wait"] == 1
    # one wait per batch taken from the queue, and the wait that met
    # the end of the stream
    assert sum(s.name == "prefetch.wait" for s in stages) == len(keys) + 1
    assert all(s.dur >= 0 for s in spans)
    # the prefetch thread prepares; the consumer waits, routes, reparses
    threads = {s.name: s.thread for s in stages}
    assert threads["prepare"] != threads["route"] == threads["reparse"] \
        == threads["prefetch.wait"]
    hists = metrics["hists"]
    for name in ("engine.prepare_s", "engine.route_s", "engine.reparse_s"):
        assert hists[name]["total"] == len(keys)
    assert hists["engine.route_s"]["sum"] == pytest.approx(
        sum(s.dur for s in stages if s.name == "route"))


def test_engine_off_plane_records_and_observes_nothing(tiny_llm):
    spans, metrics = _run_tiny(tiny_llm, False)
    assert spans == []
    assert not any(k.startswith("engine.") for k in metrics["hists"])
    assert obs.span("prepare", 0) is obs.span("route", 1, "x")
    with obs.span("cache_lookup", 2) as lookup:
        lookup.cached = True             # ignored, never raises


@pytest.mark.parametrize("kernel", [False, True])
def test_route_attention_kernel_counted_once_per_batch(tiny_llm, monkeypatch,
                                                       kernel):
    """``route.attention_kernel`` counts the batches whose route step ran
    the encoder attention kernel, plane on or off: none on the einsum path
    (this backend's), each of the 3 when the kernel is taken (interpret
    mode here)."""
    import repro.kernels.encoder_attention as ea

    if kernel:
        monkeypatch.setattr(ea, "uses_kernel", lambda force_kernel=False: True)
    _, metrics = _run_tiny(tiny_llm, False)
    assert metrics["counters"].get(obs.ROUTE_ATTENTION_KERNEL, 0) == \
        (3 if kernel else 0)


def test_plane_hooks_gc_and_compiles_until_turned_off():
    import jax
    import jax.numpy as jnp

    rec = obs.configure(True)
    base = obs.metrics().snapshot()
    try:
        with obs.span("route", 5):
            gc.collect()
        collected = rec.drain()
        with obs.span("route", 6):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0))
        compiled = rec.drain()
    finally:
        obs.configure(False)
    counters = obs.diff(obs.metrics().snapshot(), base)["counters"]
    (g,) = [s for s in collected if s.name == "gc"]
    assert g.detail.endswith(" collected") and g.dur > 0
    assert (g.parent, g.trace) == ("route", "5")
    assert counters["gc.collections.gen2"] >= 1
    compiles = [s for s in compiled if s.name == "compile"]
    assert {(s.parent, s.trace) for s in compiles} == {("route", "6")}
    assert "jit(<lambda>)" in {s.detail for s in compiles}
    assert counters["jax.compiles"] == len(compiles)
    assert rec.on_gc not in gc.callbacks
    # the hooks are gone: a later collection or compile records nothing
    gc.collect()
    jax.jit(lambda x: x * 5 + 1)(jnp.arange(7.0))
    assert rec.drain() == [] and not obs.recorder().enabled


# ---------------------------------------------------------------------------
# Span conservation on a real crash/flap worker fleet
# ---------------------------------------------------------------------------


def test_obs_off_campaign_has_no_spans(corpus, ft_router):
    ccfg, docs = corpus
    test = docs[75:107]
    ecfg = EngineConfig(alpha=0.1, batch_size=16)
    res = CampaignExecutor(ecfg, ExecutorConfig(n_nodes=2,
                                                straggler_rate=0.0),
                           ft_router, ccfg).run(test)
    assert res.spans == []
    assert not obs.recorder().enabled    # the run left the noop in place


def test_span_conservation_4worker_crash_flap(corpus, ft_router):
    """The ISSUE-9 conservation laws, on the adversarial fleet shape
    (one worker hard-crashes, another mutes then flaps back, payloads
    over shm): replaying the trace file reproduces the executor's
    counters *exactly* — the trace is an audit log of the dedup gate,
    not a sample."""
    ccfg, docs = corpus
    test = docs[75:]
    ecfg = EngineConfig(alpha=0.1, batch_size=8)
    xcfg = ExecutorConfig(
        n_nodes=4, runtime="process", prefetch_depth=2,
        transport="shm", obs=True,
        heartbeat_timeout_s=2.0, heartbeat_interval_s=0.1,
        straggler_grace_s=2.5,
        fault_injection=FaultInjection(crash_after=((2, 1),),
                                       mute_after=((1, 0),),
                                       unmute_after=((1, 2),),
                                       mute_slowdown_s=0.9))
    res = CampaignExecutor(ecfg, xcfg, ft_router, ccfg).run(test)
    assert len(res.records) == len(test)
    assert res.reissued >= 1             # the faults actually fired

    by_name = Counter(s.name for s in res.spans)
    n_batches = -(-len(test) // 8)
    # exactly one winning complete span per emitted batch...
    assert by_name["complete"] == n_batches
    # ...each for a distinct batch key (no double emission)
    complete_keys = [s.trace for s in res.spans if s.name == "complete"]
    assert len(set(complete_keys)) == n_batches
    # every dropped duplicate left a dedup span, every re-issue a
    # reissue span, and cached wins carry the flag
    assert by_name["dedup"] == res.duplicates_dropped
    assert by_name["reissue"] == res.reissued
    assert sum(s.cached for s in res.spans
               if s.name == "complete") == res.cache_hits
    assert set(by_name) <= set(obs.SPAN_STAGES)

    # the folded fleet metrics agree with the executor counters
    c = res.obs_metrics["counters"]
    assert c.get("pool.batches_done", 0) == n_batches
    assert c.get("pool.dedup_dropped", 0) == res.duplicates_dropped
    assert c.get("pool.reissued", 0) == res.reissued
    assert c.get("pool.reissued_reparse", 0) == res.reissued_reparse

    # trace-file replay: writing + re-loading loses nothing
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        obs.TraceWriter(td).write(res.spans)
        replay, meta = obs.load_spans(td)
        assert meta["n_spans"] == len(res.spans)
        assert Counter(s.name for s in replay) == by_name
        assert Counter(
            s.trace for s in replay if s.name == "complete"
        ) == Counter(complete_keys)
        json.load(open(f"{td}/trace.json"))   # Chrome trace parses


def test_local_runtime_emits_conserved_spans(corpus, ft_router):
    """The simulated LocalWorkerPool honors the same laws (cheap to
    run, so it guards the contract in the fast lane): one complete per
    batch and a reissue span per simulated straggler re-issue."""
    ccfg, docs = corpus
    test = docs[75:]
    ecfg = EngineConfig(alpha=0.1, batch_size=8)
    xcfg = ExecutorConfig(n_nodes=3, straggler_rate=0.4,
                          straggler_slowdown=6.0, deadline_factor=1.5,
                          obs=True, seed=5)
    res = CampaignExecutor(ecfg, xcfg, ft_router, ccfg).run(test)
    by_name = Counter(s.name for s in res.spans)
    assert by_name["complete"] == -(-len(test) // 8)
    assert by_name["reissue"] == res.reissued
    assert not obs.recorder().enabled    # restored after collection
