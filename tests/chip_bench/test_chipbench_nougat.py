"""The ``nougat-bulk`` cell rehearsed on the CPU: the configuration's
Nougat parser model (``configs/nougat_parser.py``) at the tiny preset
runs through the engine's own ``complete_batch``, its readings are
judged by the configuration's limits, a fault in what the decode kept
fails them, the float8 control fails them, and a traced run reads the
parser's spans and counters."""
import numpy as np
import pytest
from chipbench_tiny import tiny_cell

import calibrate
import check
import harness

#: float32 program against the float32 reference on the CPU: readings
#: of about 1e-6; the control reads above 0.1
TINY_PARSER_LIMITS = {"parse_logit_gap": 1e-4}
SPAN_READERS = ("parse_encode_ms", "parse_decode_ms",
                "decode_slot_occupancy")


@pytest.fixture(scope="module")
def nougat_cell():
    """``nougat-bulk`` at the CPU's size: the tiny router and batches of
    ``chipbench_tiny``, and Nougat's tiny preset as the parser model."""
    from repro.core.parser_model import parser_config

    name, config, traffic, e2e, per_layer = tiny_cell("nougat-bulk")
    preset = parser_config("reduced")
    widths = {f: getattr(preset, f) for f in preset.__dataclass_fields__
              if f != "name"}
    config = dict(config, parser_model=dict(reference="nougat_parser",
                                            **widths),
                  limits=dict(config["limits"], **TINY_PARSER_LIMITS))
    return name, config, traffic, e2e, per_layer


def _run(cell, seed, trace=False, seconds=2.0):
    return harness.run_cell(*cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=0.0)


def test_nougat_cell_is_correct_untraced(nougat_cell):
    from repro.core import backends

    channel = backends.get_backend("nougat")
    res = _run(nougat_cell, 2 ** 31 + 61)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(nougat_cell[1]["limits"])
    gap, _ = res["checks"]["parse_logit_gap"]
    assert 0 < gap < 1e-5
    assert res["checks"]["parse_fed_diff"][0] == 0
    assert res["checks"]["parse_page_diff"][0] == 0
    assert res["checks"]["parse_step_diff"][0] == 0
    assert sorted(res["metrics"]) == ["batch_p90_ms", "docs_per_s",
                                      "setup_s"]
    assert backends.get_backend("nougat") is channel


def test_traced_run_reads_the_parsers_spans_and_counters(nougat_cell,
                                                         monkeypatch):
    runs = []

    class Run(harness.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)
    monkeypatch.setattr(harness, "Run", Run)
    res = _run(nougat_cell, 2 ** 31 + 67, trace=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert set(SPAN_READERS) <= set(got)
    assert got["parse_decode_ms"]["value"] > 0
    assert got["parse_encode_ms"]["value"] > 0
    assert 0 < got["decode_slot_occupancy"]["value"] < 1
    # no TPU trace and no peak on the CPU: the device shares stay silent
    assert "decode_hbm_roofline" not in got
    assert "swin_encoder_mfu" not in got
    (run,) = runs
    c = run.counters
    slots = nougat_cell[1]["parser_model"]["decode_slots"]
    assert c["parse.slot_steps"] == slots * c["parse.decode_steps"]
    assert c["parse.pages"] > 0 and c["parse.live_kv_positions"] > 0
    assert {"parse.render", "parse.encode", "parse.decode",
            "parse.wait"} <= set(run.spans.spans)


def _perturbed(keep):
    def fault(backend, row):
        out = keep(backend, row)
        for cap in out["captures"]:
            cap["logits"] = cap["logits"] + 0.05 * cap["logits"].std()
            cap["logits"][:, 0] += cap["logits"].std()
        return out
    return fault


def _page_dropped(keep):
    def fault(backend, row):
        out = keep(backend, row)
        if out["pages"]:
            out["pages"], out["steps"] = out["pages"][1:], out["steps"][1:]
        return out
    return fault


def _step_short(keep):
    def fault(backend, row):
        out = keep(backend, row)
        out["steps"] = [s - 1 for s in out["steps"]]
        return out
    return fault


def _last_token_other(keep):
    """A decode whose last token is not its own argmax: the logits and
    every fed token stay as they were, so only the fed count sees it."""
    def fault(backend, row):
        out = keep(backend, row)
        for cap in out["captures"]:
            cap["tokens"][-1] = (cap["tokens"][-1] + 1) \
                % cap["logits"].shape[-1]
        return out
    return fault


@pytest.mark.parametrize("fault,number", [
    (_perturbed, "parse_logit_gap"), (_page_dropped, "parse_page_diff"),
    (_step_short, "parse_step_diff"), (_last_token_other, "parse_fed_diff")],
    ids=["perturbed_logits", "page_dropped", "one_step_short",
         "last_token_not_argmax"])
def test_fault_in_the_decode_is_not_correct(nougat_cell, monkeypatch, fault,
                                            number):
    load = harness.load_module

    def load_module(path):
        mod = load(path)
        if path.stem == "nougat_parser":
            mod.keep = fault(mod.keep)
        return mod
    monkeypatch.setattr(harness, "load_module", load_module)
    res = _run(nougat_cell, 2 ** 31 + 71)
    assert not res["correct"]
    value, limit = res["checks"][number]
    assert value > limit


def test_float8_control_is_not_correct(nougat_cell):
    _, config, traffic, _, _ = nougat_cell
    numbers = calibrate.control(config, traffic, 2 ** 31 + 73)
    ok, checks = check.judge(numbers, config["limits"])
    assert not ok
    value, limit = checks["parse_logit_gap"]
    assert value > 100 * limit
    assert checks["parse_page_diff"][0] == checks["parse_step_diff"][0] == 0
    assert checks["parse_fed_diff"][0] == 0
    assert np.isfinite(numbers["parse_token_regret"])
