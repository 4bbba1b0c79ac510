"""What makes a chip-benchmark run come out not correct, at a tiny size
on the CPU: the control (the reference one precision below the
configuration's, put in the program's place) and faults planted in the
timed path underneath a whole run — an answer altered where it is
produced (features, the routed set, an emitted token), and half of a
probed batch left out with the mean taken over the rest."""
import dataclasses

import numpy as np
import pytest
from chipbench_tiny import CELLS, tiny_cell

import calibrate
import check
import harness


@pytest.mark.parametrize("workload", list(CELLS))
def test_control_is_not_correct(workload):
    _, config, traffic, _, _ = tiny_cell(CELLS[workload])
    numbers = calibrate.control(config, traffic, 2 ** 31 + 23)
    ok, checks = check.judge(numbers, config["limits"])
    assert not ok
    failing = {k for k, (v, lim) in checks.items() if v > lim}
    assert "feature_gap" in failing
    if workload == "llm-bulk":
        assert "pred_acc_gap" in failing


def _features(monkeypatch):
    from repro.core.engine import AdaParseEngine

    orig = AdaParseEngine.prepare_batch

    def prepare_batch(self, docs, batch_key=None):
        prep = orig(self, docs, batch_key=batch_key)
        prep.fast[0, 1] += 1e-3
        return prep
    monkeypatch.setattr(AdaParseEngine, "prepare_batch", prepare_batch)
    return "feature_gap"


def _routed_set(monkeypatch):
    from repro.core.engine import AdaParseEngine

    orig = AdaParseEngine.route_batch

    def route_batch(self, prep):
        plan = orig(self, prep)
        free = np.setdiff1d(np.arange(len(prep.docs)), plan.expensive_idx)
        sel = np.sort(np.concatenate([plan.expensive_idx[1:], free[:1]]))
        return dataclasses.replace(plan, expensive_idx=sel.astype(np.int64))
    monkeypatch.setattr(AdaParseEngine, "route_batch", route_batch)
    return "select_diff"


def _emitted_token(monkeypatch):
    from repro.core.engine import AdaParseEngine

    orig = AdaParseEngine.complete_batch

    def complete_batch(self, prep, plan, **kw):
        records = orig(self, prep, plan, **kw)
        r = next(r for r in records if any(len(p) for p in r.pages))
        i = next(i for i, p in enumerate(r.pages) if len(p))
        r.pages = list(r.pages)
        r.pages[i] = np.concatenate([[r.pages[i][0] ^ 1], r.pages[i][1:]])
        return records
    monkeypatch.setattr(AdaParseEngine, "complete_batch", complete_batch)
    return "record_diff"


def _half_probe(monkeypatch):
    from repro.core.quality import QualityProbe

    orig = QualityProbe.score_records

    def score_records(self, docs, records):
        half = len(docs) // 2
        return orig(self, docs[:half], records[:half])
    monkeypatch.setattr(QualityProbe, "score_records", score_records)
    return "probe_diff"


@pytest.mark.parametrize("fault", [_features, _routed_set, _emitted_token,
                                   _half_probe],
                         ids=["features", "routed_set", "emitted_token",
                              "half_probe"])
@pytest.mark.parametrize("workload", list(CELLS))
def test_fault_in_the_timed_path_is_not_correct(workload, fault,
                                                monkeypatch):
    number = fault(monkeypatch)
    name, config, traffic, e2e, per_layer = tiny_cell(CELLS[workload])
    res = harness.run_cell(name, config, traffic, e2e, per_layer,
                           seed=2 ** 31 + 29, seconds=2.0, trace=False,
                           t_start=0.0)
    assert not res["correct"]
    value, limit = res["checks"][number]
    assert value > limit
