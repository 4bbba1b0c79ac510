"""The chip benchmark's parts that need no run: BENCHMARK.json against
its own rules, finding configurations, traffic mixes and metrics by
name, the operation and byte counters checked by hand at SciBERT
widths, the peaks table, the traffic generator, and the trace
reduction on a small trace recorded on a TPU v5e chip."""
import json
import re
import shutil

import numpy as np
import pytest
from chipbench_tiny import BENCH, ROOT, benchmark

import devtrace
import flops
import harness
import traffic as T

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SCIBERT = dict(n_layers=12, d_model=768, n_heads=12, d_ff=3072,
               n_outputs=6)


def test_benchmark_file_keeps_its_rules():
    b = benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    assert "setup_s" in e2e and 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert NAME.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert json.loads((ROOT / c["file"]).read_text())["name"] \
            == c["name"]
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")


#: every workload, and the configuration and traffic files that have
#: none yet (PERF.md, open questions)
CELLS = ([w["name"] for w in benchmark()["workloads"]]
         + ["ft-router/bulk", "scibert-router-llm/long-docs"])


@pytest.mark.parametrize("workload", CELLS)
def test_cells_resolve_by_name(workload):
    entry = next((w for w in benchmark()["workloads"]
                  if w["name"] == workload), None)
    name, config, traffic, e2e, per_layer = harness.resolve_cell(
        benchmark(), workload if entry else None,
        None if entry else workload)
    want = ((entry["config"], entry["traffic"]) if entry
            else tuple(workload.split("/")))
    assert (config["name"], traffic["name"]) == want
    assert {m["name"] for m in e2e} >= {"setup_s"}
    for m in per_layer:
        assert callable(harness.metric_reader(m["name"]))
    assert traffic["pool_docs"] % config["batch_size"] == 0
    # the mix packs to the widths it warms, and fills the encoder's rows
    assert all(w >= config.get("encoder", {}).get("max_len", 0)
               for w in traffic["packed_widths"])
    if "encoder" in config:
        low = traffic["page_tokens"] * (1 - traffic["page_token_spread"])
        assert low >= config["encoder"]["max_len"] - 1


def test_new_files_are_found_without_editing_any(tmp_path, monkeypatch):
    copy = tmp_path / "chip"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("out"))
    bulk = json.loads((copy / "traffic" / "bulk.json").read_text())
    (copy / "traffic" / "short-docs.json").write_text(json.dumps(
        dict(bulk, name="short-docs", max_pages=2)))
    ft = json.loads((copy / "configs" / "ft-router.json").read_text())
    (copy / "configs" / "ft-router-a10.json").write_text(json.dumps(
        dict(ft, name="ft-router-a10", alpha=0.1)))
    (copy / "metrics" / "docs_total.py").write_text(
        "def read(run):\n    return float(len(run.batches))\n")
    # a device-trace reader names its operation and counts its own work
    (copy / "metrics" / "copy_roofline.py").write_text(
        "def read(run):\n"
        "    return run.roofline('copy', [(0.0, 4.0 * r['n_docs'])\n"
        "                                 for r in run.started('prepare')])\n")
    monkeypatch.setattr(harness, "BENCH", copy)
    new = [{"name": "docs_total", "unit": "count", "better": "higher",
            "source": "host_clock", "layer": "batch loop",
            "moves": "docs_per_s"},
           {"name": "copy_roofline", "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "device",
            "moves": "docs_per_s"}]
    bench = dict(benchmark(), per_layer=benchmark()["per_layer"] + new)
    name, config, traffic, _, per_layer = harness.resolve_cell(
        bench, None, "ft-router-a10/short-docs")
    assert (config["alpha"], traffic["max_pages"]) == (0.1, 2)
    assert {"docs_total", "copy_roofline"} <= {m["name"] for m in per_layer}
    rec, events = _events()
    trace = devtrace.reduce(events, rec["seconds"])
    calls = [{"n_docs": 256, "t_prepare": 0.5}, {"n_docs": 256,
                                                 "t_prepare": 2.0}]
    run = harness.Run({}, {}, [{}] * 3, calls, (0.0, 1.0), 0,
                      harness.device_peak("TPU v5 lite"), trace)
    assert harness.metric_reader("docs_total")(run) == 3.0
    # one call started in the window: 1024 bytes over the copies' time
    seconds, n = trace.ops["copy"]
    assert n > 0
    assert harness.metric_reader("copy_roofline")(run) == pytest.approx(
        100.0 * 1024 / 819e9 / seconds)
    with pytest.raises(LookupError):
        harness.resolve_cell(bench, None, "ft-router/no-such-mix")
    with pytest.raises(LookupError):
        harness.resolve_cell(bench, "no-such-cell", None)


def test_seeded_head_gives_the_configured_share_of_improvements():
    """Seeded weights put nearly every document on one side of zero on
    some seeds; the centred head routes by a real ranking on every one."""
    from chipbench_tiny import tiny_cell

    import reference as R

    _, config, traffic, _, _ = tiny_cell("llm-bulk")
    encoder = harness.load_module(BENCH / "configs" / "bert_encoder.py")
    for seed in (3, 2 ** 31 + 7):
        stages = harness.fit_stages(config, traffic, seed)
        weights = harness.seeded_weights(config, encoder, seed, stages)
        pred = encoder.predict(weights, config["encoder"],
                               *stages["first_pages"])
        imp = (pred[:, config["expensive_index"]]
               - pred[:, config["cheap_index"]])
        share = np.mean(imp > R.POSITIVE_TAU)
        assert abs(share - config["positive_share"]) <= 2 / len(imp)


def test_encoder_flops_by_hand_at_scibert_widths():
    # per token 12 * 2 * (4 * 768^2 + 2 * 768 * 3072) = 169,869,312;
    # attention 12 * 4 * 256^2 * 768; pooler and head 2*768^2 + 2*768*6
    assert flops.encoder_flops([256], SCIBERT) == 45_903_651_840
    # padding is not work: a 1-token document costs its own token only
    assert flops.encoder_flops([1], SCIBERT) == (
        169_869_312 + 12 * 4 * 768 + 1_188_864)
    assert flops.encoder_flops([256, 256], SCIBERT) == 2 * 45_903_651_840


def test_kernel_work_by_hand():
    # 1024 tokens read (4 B), 2 docs x (16 B in + 32 B features
    # + 512 x 8 B tokens and mask out)
    assert flops.fast_features_work([1000, 24], 512) == (10_240.0,
                                                         12_384.0)
    assert flops.fast_features_work([1000, 24], 0) == (10_240.0, 4_192.0)
    # 556 tokens, 4 orders x 4 operations; 2 docs x (8 B lengths + 4 B)
    assert flops.ngram_work([256, 100], [200, 0]) == (8_896.0, 2_248.0)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_share(197e9, 0.0, 1.0, peak) == (
        pytest.approx(0.1), "ops")
    assert flops.roofline_share(0.0, 819e6, 0.5, peak) == (
        pytest.approx(0.2), "bytes")


def test_peaks_table_names_its_source_and_refuses_unknown_devices():
    peak = harness.device_peak("TPU v5 lite")
    assert (peak["flops_per_s"], peak["hbm_bytes_per_s"]) == (197e12,
                                                              819e9)
    assert "TPU v5e" in peak["source"]
    with pytest.raises(LookupError):
        harness.device_peak("TPU v9")


def test_every_seed_draws_the_same_sizes():
    from repro.data.synthetic import Document

    mix = json.loads((BENCH / "traffic" / "bulk.json").read_text())
    a, b = (T.make_pool(mix, mix["corpus"], s, Document, n_docs=64)
            for s in (2 ** 31 + 1, 5))
    assert sorted(d.n_pages for d in a) == sorted(d.n_pages for d in b)
    assert sum(d.scanned for d in a) == sum(d.scanned for d in b) == 10
    again = T.make_pool(mix, mix["corpus"], 5, Document, n_docs=64)
    assert all(np.array_equal(x, y) for d, e in zip(b, again)
               for x, y in zip(d.pages, e.pages))
    keys = [k for k, _ in zip(T.batches(b, 32, 5, first_key=7), range(5))]
    assert [k for k, _ in keys] == [7, 8, 9, 10, 11]
    assert sorted(d.doc_id for _, docs in keys[:2] for d in docs) \
        == list(range(64))


def _events():
    rec = json.loads((BENCH / "testdata" / "trace_excerpt.json").read_text())
    return rec, [devtrace.Event(*e[:5], tuple(map(tuple, e[5])))
                 for e in rec["events"]]


def test_trace_reduction_on_a_recorded_trace():
    rec, events = _events()
    got = devtrace.reduce(events, rec["seconds"])
    want = rec["expected"]
    assert got.window_s == pytest.approx(rec["seconds"])
    assert got.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    for k, (s, n) in want["ops"].items():
        assert got.ops[k][1] == n
        assert got.ops[k][0] == pytest.approx(s, rel=1e-9)
    assert [n for n, _ in got.breakdown["idle_gaps"]] \
        == [n for n, _ in want["idle_gaps"]]
    assert sum(s for _, s in got.breakdown["idle_gaps"]) == pytest.approx(
        got.window_s - got.busy_s)


def test_trace_reduction_by_hand():
    ev = devtrace.Event
    dev, host, ops, mods = ("/device:TPU:0", "/host:CPU", devtrace.OPS_LINE,
                            devtrace.MODULES_LINE)
    events = [
        ev(host, "main", devtrace.WINDOW_MARK, 1_000.0, 0.0),
        ev(host, "main", "bench.route", 1_000.0, 4_000.0),
        ev(host, "worker", "bench.prepare", 0.0, 20_000.0),
        ev(dev, mods, "jit_route_step(42)", 1_400.0, 1_700.0),
        ev(dev, ops, "%while = (s32[]) while(...)", 1_450.0, 1_600.0),
        ev(dev, ops, "%fusion.1 = f32[8] fusion(...)", 1_500.0, 1_000.0),
        ev(dev, ops, "%fusion.2 = f32[8] fusion(...)", 2_000.0, 1_000.0),
        ev(dev, mods, "jit_fast_features_kernel(7)", 5_900.0, 2_200.0),
        ev(dev, ops, "%fast_features_kernel.1 = f32[256,8] custom-call()",
           6_000.0, 2_000.0),
        ev(dev, ops, "%late = f32[8] fusion(...)", 20_000.0, 5_000.0),
    ]
    got = devtrace.reduce(events, 10e-6)
    assert got.window_s == pytest.approx(10e-6)
    # busy: 1450-3050 and 6000-8000 (the late op is past the window)
    assert got.busy_s == pytest.approx(3.6e-6)
    assert got.ops["fast_features_kernel"] == (pytest.approx(2e-6), 1)
    assert "ngram_bleu_kernel" not in got.ops
    assert got.ops["fusion"] == (pytest.approx(2e-6), 2)
    # the loop holds its body's time and is left out of the top list
    assert dict(got.breakdown["device_ops"]) == {
        "fast_features_kernel/fast_features_kernel.1": pytest.approx(2e-6),
        "route_step/fusion.1": pytest.approx(1e-6),
        "route_step/fusion.2": pytest.approx(1e-6)}
    # each gap goes to the span over its middle: 1000-1450 and
    # 3050-6000 to route, 8000-11000 to prepare alone
    assert dict(got.breakdown["idle_gaps"]) == {
        "route": pytest.approx(3.4e-6), "prepare": pytest.approx(3e-6)}
    assert devtrace.reduce(events[:3], 10e-6) is None
