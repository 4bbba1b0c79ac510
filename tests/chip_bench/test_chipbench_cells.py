"""CPU rehearsal of the chip benchmark: its refusal off the TPU, and a
whole run of each cell at a tiny size, with the Pallas kernels of the
prepare stage and the probe in interpret mode, untraced and traced."""
import importlib.util
import json

import pytest
from chipbench_tiny import BENCH, CELLS, tiny_cell

import harness


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Run fast_features and ngram_score as Pallas kernels (interpret
    mode) where the CPU would take their host oracles."""
    from repro.core import features, metrics

    prepare = features.prepare_routing_inputs
    score = metrics.ngram_bleu
    monkeypatch.setattr(features, "prepare_routing_inputs",
                        lambda *a, **k: prepare(*a, **{**k,
                                                       "mode": "force"}))
    monkeypatch.setattr(metrics, "ngram_bleu",
                        lambda *a, **k: score(*a, force_kernel=True, **k))


def test_refuses_a_host_without_tpu(capsys):
    spec = importlib.util.spec_from_file_location("cell", BENCH / "cell.py")
    cell = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cell)
    assert cell.main(["--workload", "llm-bulk", "--seed", "3",
                      "--seconds", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "TPU" in err


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
@pytest.mark.parametrize("workload", list(CELLS))
def test_cell_runs_at_a_tiny_size(workload, trace, interpret_kernels):
    name, config, traffic, e2e, per_layer = tiny_cell(CELLS[workload])
    res = harness.run_cell(name, config, traffic, e2e, per_layer,
                           seed=2 ** 31 + 17, seconds=3.0,
                           trace=bool(trace), t_start=0.0)
    json.dumps(res)
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["checks"]) == set(config["limits"])
    got = set(res["metrics"])
    if trace:
        # host-clock and program-span readers read on any backend; the
        # device ones find no TPU trace on the CPU and stay silent
        assert got == {"window_compiles", "prepare_ms", "route_ms",
                       "complete_ms", "probe_ms", "prefetch_wait_ms",
                       "prepare_host_ms", "prepare_wait_ms",
                       "route_wait_ms", "gc_ms", "starved_frac"}
        assert res["metrics"]["window_compiles"]["value"] == 0
    else:
        assert got == {m["name"] for m in e2e}
        assert all(v["value"] > 0 for v in res["metrics"].values())
