"""A configuration that brings its own parser model (``parser_model``):
the harness finds its module by name, builds it, runs it through the
engine's own ``complete_batch`` and judges its readings by the
configuration's limits; a fault in what the model keeps fails the run.
A configuration without one gives what it gave before the hooks
existed. Traced runs read the program's spans and counters."""
import json
import shutil

import numpy as np
import pytest
from chipbench_tiny import BENCH, CELLS, tiny_cell

import calibrate
import check
import harness
import spantrace

HERE = BENCH.parents[1] / "tests" / "chip_bench"
SPAN_READERS = ("prefetch_wait_ms", "prepare_host_ms", "prepare_wait_ms",
                "route_wait_ms", "gc_ms", "starved_frac")
#: between the stub's float32 op against float64 (about 1e-7 on the
#: CPU) and its float16 control (about 1e-3)
STUB_LIMITS = {"stub_gap": 1e-5, "stub_docs_diff": 0}


@pytest.fixture
def stub_cell(tmp_path, monkeypatch):
    """The tiny ``llm-bulk`` cell with the stub parser model, its module
    in a copy of the benchmark's ``configs/`` (no file of the benchmark
    edited); yields the cell and the loaded parser models."""
    copy = tmp_path / "chip"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("out"))
    shutil.copy(HERE / "stub_parser.py", copy / "configs" / "stub_parser.py")
    monkeypatch.setattr(harness, "BENCH", copy)
    built = []
    orig = harness.parser_model

    def parser_model(config, seed):
        built.append(orig(config, seed))
        return built[-1]
    monkeypatch.setattr(harness, "parser_model", parser_model)
    name, config, traffic, e2e, per_layer = tiny_cell(CELLS["llm-bulk"])
    config = dict(config, parser_model={"reference": "stub_parser", "d": 16,
                                        "rows": 8},
                  limits=dict(config["limits"], **STUB_LIMITS))
    return (name, config, traffic, e2e, per_layer), built


def _run(cell, seed, seconds=2.0, trace=False):
    return harness.run_cell(*cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=0.0)


def test_parser_model_runs_through_the_engine_and_is_judged(stub_cell):
    from repro.core import backends

    cell, built = stub_cell
    channel = backends.get_backend("nougat")
    res = _run(cell, 2 ** 31 + 43)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(cell[1]["limits"])
    gap, limit = res["checks"]["stub_gap"]
    assert 0 < gap <= limit
    assert res["checks"]["stub_docs_diff"] == [0, 0]
    (parser,) = built
    assert parser.module.CALLS == ["init", "backend", "warm", "keep",
                                   "readings"]
    # the program's own backend is back in its place after the run
    assert backends.get_backend("nougat") is channel


def _perturbed(keep):
    def fault(backend, row):
        ids, out = keep(backend, row)
        return ids, out + np.eye(*out.shape) * 1e-3
    return fault


def _half_the_documents(keep):
    def fault(backend, row):
        ids, out = keep(backend, row)
        return ids[: len(ids) // 2], out
    return fault


@pytest.mark.parametrize("fault,number", [(_perturbed, "stub_gap"),
                                          (_half_the_documents,
                                           "stub_docs_diff")],
                         ids=["perturbed_output", "half_the_documents"])
def test_fault_in_what_keep_stores_is_not_correct(stub_cell, monkeypatch,
                                                  fault, number):
    cell, _ = stub_cell
    load = harness.load_module

    def load_module(path):
        mod = load(path)
        if path.stem == "stub_parser":
            mod.keep = fault(mod.keep)
        return mod
    monkeypatch.setattr(harness, "load_module", load_module)
    res = _run(cell, 2 ** 31 + 47)
    assert not res["correct"]
    value, limit = res["checks"][number]
    assert value > limit


def test_parser_model_control_is_not_correct(stub_cell):
    (_, config, traffic, _, _), _ = stub_cell
    numbers = calibrate.control(config, traffic, 2 ** 31 + 53)
    ok, checks = check.judge(numbers, config["limits"])
    assert not ok
    value, limit = checks["stub_gap"]
    assert value > 10 * limit


WITHOUT_PARSER = json.loads(
    (HERE / "testdata" / "tiny_results_without_parser_model.json")
    .read_text())


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
@pytest.mark.parametrize("workload", list(CELLS))
def test_config_without_parser_model_keeps_its_results(workload, trace):
    cell = tiny_cell(CELLS[workload])
    assert "parser_model" not in cell[1]
    res = _run(cell, WITHOUT_PARSER["seed"], seconds=0.0,
               trace=bool(trace))
    want = WITHOUT_PARSER["results"][f"{CELLS[workload]} trace {trace}"]
    assert list(res) == want["keys"]
    assert {k: res[k] for k in ("correct", "attempted", "failed")} == {
        k: want[k] for k in ("correct", "attempted", "failed")}
    assert res["checks"] == want["checks"]
    if not trace:
        assert sorted(res["metrics"]) == want["metrics"]


@pytest.mark.parametrize("workload", list(CELLS))
def test_traced_run_reads_the_programs_spans_and_counters(workload,
                                                          monkeypatch):
    runs = []

    class Run(harness.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)
    monkeypatch.setattr(harness, "Run", Run)
    res = _run(tiny_cell(CELLS[workload]), 2 ** 31 + 59, trace=True)
    assert res["correct"], res["checks"]
    (run,) = runs
    spans = run.spans
    assert isinstance(spans, spantrace.Spans)
    assert {"prepare", "prepare.wait", "prefetch.wait"} <= set(spans.spans)
    read = {m: harness.metric_reader(m)(run) for m in SPAN_READERS}
    assert all(isinstance(v, float) for v in read.values()), read
    assert read["prepare_host_ms"] > 0 and read["prefetch_wait_ms"] > 0
    if workload == "llm-bulk":
        assert read["route_wait_ms"] > 0
    # no device trace on the CPU: the whole window is idle, and every
    # idle second is charged to some span
    busy = spans.window_s - sum(b - a for a, b in spans.idle)
    assert sum(spans.idle_by_span.values()) == pytest.approx(
        spans.window_s - busy, rel=0.01)
    assert run.counters.get("gc.collections.gen0", 0) > 0
    # the plane is off again once the window has closed
    from repro.core import obs

    assert not obs.recorder().enabled
