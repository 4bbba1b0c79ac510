"""CPU rehearsal of ``chip_smoke.py``: its off-TPU refusal, and each of
its phases at a tiny size (kernels in interpret mode, the campaign with
the reduced router), so the smoke's checks and the engine hooks it
wraps stay in step with the code."""
import importlib.util
import pathlib

import pytest

SMOKE = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_tpu(smoke, capsys):
    assert smoke.main() == 1
    out, err = capsys.readouterr()
    assert out == "" and "not a TPU" in err


@pytest.mark.parametrize("phase,args", [
    ("budget_route_parity", (256, 128)),
    ("fast_features_parity", (16, 64)),
    ("ngram_score_parity", (16, 128)),
], ids=["budget_route", "fast_features", "ngram_score"])
def test_kernel_phase_in_interpret_mode(smoke, capsys, phase, args):
    getattr(smoke, phase)(*args, interpret=True)
    assert "compile_s=" in capsys.readouterr().out


def test_campaign_phase_with_reduced_router(smoke, capsys):
    """serve.main --variant llm end to end: 144 documents leave a
    96-document test split, 3 route batches of 32."""
    smoke.campaign(["--variant", "llm", "--docs", "144", "--batch-size",
                    "32", "--alpha", "0.1", "--adaptive-rounds", "2",
                    "--quality-probe-rate", "0.5"],
                   route_batch=32, n_batches=3, published=False)
    out = capsys.readouterr().out
    assert "selected_idx == scheduler.plan_batch" in out
    assert "96 records for 96 test docs" in out
