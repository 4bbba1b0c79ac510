"""The scenario lab (core/scenarios): a named, deterministic stress-
scenario matrix over the local, process and fabric worker runtimes.
Tier-1 runs every scenario end-to-end — each asserts the
byte-identical-records invariant against its single-node reference
inside ``run_scenario`` — plus the registry/runner contracts."""
import dataclasses

import numpy as np
import pytest

from repro.core.scenarios import (SCENARIOS, ScenarioMismatch,
                                  ScenarioSpec, _assert_records_match,
                                  get_scenario, run_scenario)

REQUIRED = {"crash_storm", "wedged_straggler_flap", "bursty_arrivals",
            "bimodal_retune", "cold_warm_shared_store", "slowdown_skew",
            "shm_crash_reissue", "elastic_join_leave"}


def test_registry_ships_the_scenario_matrix():
    """At least the six ISSUE-6 scenarios plus the shm-transport crash
    scenario and the elastic fabric scenario, each fully declarative
    and self-describing."""
    assert REQUIRED <= set(SCENARIOS)
    assert len(SCENARIOS) >= 8
    for name, spec in SCENARIOS.items():
        assert spec.name == name
        assert isinstance(spec, ScenarioSpec) and spec.description
        assert spec.runtime in ("local", "process", "fabric")
        assert spec.transport in ("shm", "pickle")
    assert SCENARIOS["shm_crash_reissue"].transport == "shm"
    assert SCENARIOS["shm_crash_reissue"].fault is not None
    elastic = SCENARIOS["elastic_join_leave"]
    assert elastic.runtime == "fabric"
    assert elastic.fault is not None       # the mid-campaign crash
    assert elastic.fabric is not None      # the join + reject schedule
    assert elastic.fabric.join_after and elastic.fabric.reject >= 1


def test_get_scenario_unknown_name_is_actionable():
    with pytest.raises(KeyError, match="crash_storm"):
        get_scenario("no_such_scenario")


@pytest.mark.parametrize("name", SCENARIOS)
def test_fast_scenarios_end_to_end(name):
    """Every scenario, on whichever runtime it names (local, process or
    loopback fabric), runs its fleet, survives its adversarial schedule,
    and reproduces the single-node reference byte-for-byte
    (run_scenario raises ScenarioMismatch otherwise)."""
    res = run_scenario(SCENARIOS[name])
    assert res.records_match and res.runtime == SCENARIOS[name].runtime
    assert res.n_docs > 0 and res.goodput_docs_per_s > 0
    m = res.metrics()
    for key in ("records_match", "goodput_docs_per_s", "reissued",
                "duplicates_dropped", "cache_hits", "cache_misses"):
        assert key in m


def test_slowdown_skew_exercises_reissue():
    """The pathological-skew scenario actually trips the local
    runtime's deadline re-issue path (otherwise it guards nothing)."""
    res = run_scenario(SCENARIOS["slowdown_skew"])
    assert res.reissued >= 1


def test_bimodal_retune_moves_alpha():
    """The bimodal corpus + full-rate probe produce a live α
    trajectory (the retuner reacts), and parity still holds against
    the n_nodes=1 controller reference."""
    res = run_scenario(SCENARIOS["bimodal_retune"])
    assert res.rounds == SCENARIOS["bimodal_retune"].rounds
    assert len(res.alpha_trajectory) == res.rounds
    assert len(set(res.alpha_trajectory)) > 1


def test_record_mismatch_raises_scenario_mismatch():
    """The determinism assert fires on any divergence: a missing doc,
    a different parser, or different page payloads."""
    from repro.core.engine import ParseRecord

    def rec(i, parser="pymupdf", fill=0):
        return ParseRecord(i, parser,
                           [np.full(8, fill, np.int32)], 1.0)

    ref = {0: rec(0), 1: rec(1)}
    _assert_records_match("t", ref, {0: rec(0), 1: rec(1)})
    with pytest.raises(ScenarioMismatch, match="doc ids"):
        _assert_records_match("t", ref, {0: rec(0)})
    with pytest.raises(ScenarioMismatch, match="diverged"):
        _assert_records_match("t", ref,
                              {0: rec(0), 1: rec(1, parser="nougat")})
    with pytest.raises(ScenarioMismatch, match="diverged"):
        _assert_records_match("t", ref, {0: rec(0), 1: rec(1, fill=7)})


def test_spec_overrides_stay_declarative():
    """Specs are frozen dataclasses: a tweaked copy runs without
    touching the registry (the serve.py --scenario contract)."""
    spec = dataclasses.replace(SCENARIOS["bursty_arrivals"], rounds=1,
                               arrival_skew=((4.0, 1.0, 1.0, 0.5),))
    res = run_scenario(spec)
    assert res.records_match and res.rounds == 1
    with pytest.raises(dataclasses.FrozenInstanceError):
        SCENARIOS["bursty_arrivals"].rounds = 5
