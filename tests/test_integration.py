"""Integration tests: train loop + checkpoint/restart + elastic restore,
optimizers, pipeline parallelism, compressed collectives, serve driver."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.optim import adafactor, adamw, apply_updates


def test_adamw_and_adafactor_reduce_quadratic():
    def loss(p):
        return jnp.sum(jnp.square(p["w"] - 3.0)) + jnp.sum(
            jnp.square(p["b"] + 1.0))

    # adafactor's sign-like updates need a decaying lr to settle
    for opt in (adamw(0.1), adafactor(lambda s: 0.5 / (1.0 + 0.05 * s))):
        params = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
        state = opt.init(params)
        for step in range(200):
            g = jax.grad(loss)(params)
            upd, state = opt.update(g, state, params, jnp.asarray(step))
            params = apply_updates(params, upd)
        assert float(loss(params)) < 1e-2


def test_adafactor_state_is_factored():
    opt = adafactor(1e-2, min_dim_factored=8)
    params = {"big": jnp.zeros((16, 32)), "small": jnp.zeros((4,))}
    st = opt.init(params)
    assert set(st["v"]["big"].keys()) == {"vr", "vc"}
    assert st["v"]["big"]["vr"].shape == (16,)
    assert st["v"]["big"]["vc"].shape == (32,)
    assert set(st["v"]["small"].keys()) == {"v"}


@pytest.mark.slow
def test_train_restart_is_exact():
    """Crash/restart from checkpoint reproduces the uninterrupted run
    bit-for-bit (fault tolerance + stateless data pipeline)."""
    from repro.launch.train import main as train_main
    with tempfile.TemporaryDirectory() as d:
        ck = os.path.join(d, "ck")
        full = train_main(["--arch", "qwen3-1.7b", "--shape", "train_4k",
                           "--reduced", "--steps", "6", "--log-every", "100"])
        # interrupted run: 3 steps, checkpoint, then resume to 6
        train_main(["--arch", "qwen3-1.7b", "--shape", "train_4k",
                    "--reduced", "--steps", "3", "--ckpt-dir", ck,
                    "--ckpt-every", "3", "--log-every", "100"])
        resumed = train_main(["--arch", "qwen3-1.7b", "--shape", "train_4k",
                              "--reduced", "--steps", "6", "--ckpt-dir", ck,
                              "--ckpt-every", "100", "--log-every", "100"])
        np.testing.assert_allclose(full[3:], resumed, rtol=1e-5)


def test_checkpoint_elastic_restore():
    """Restore onto a different mesh shape (elastic rescale)."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >1 device (XLA_FLAGS host platform count)")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    mesh1 = make_mesh((2,), ("data",))
    tree = {"w": jnp.arange(32.0).reshape(8, 4)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, tree)
        sh = {"w": NamedSharding(mesh1, P("data"))}
        step, restored, _ = ckpt.restore(d, shardings=sh)
        assert restored["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))


def test_serve_driver_end_to_end():
    from repro.launch.serve import main as serve_main
    res = serve_main(["--docs", "120", "--alpha", "0.05",
                      "--variant", "ft", "--batch-size", "32"])
    assert res["bleu"] > 0.3
    assert res["frac_expensive"] <= 0.05 + 1e-9
    assert res["coverage"] > 0.8


def test_serve_llm_variant_end_to_end(monkeypatch):
    """serve --variant llm (reduced router) through the adaptive
    controller and the quality probe: 144 documents leave a 96-document
    test split in 3 route batches of 32. One record per test document,
    the expensive share within alpha, at least one probed batch, and the
    campaign's plan for the first batch equal to scheduler.plan_batch
    on that route step's own improvement output."""
    from repro.core import scheduler
    from repro.core.engine import AdaParseEngine
    from repro.core.quality import QualityProbe
    from repro.launch.serve import main as serve_main

    routed, probed, evaluated = [], [], []
    device_plan = AdaParseEngine._device_plan
    score_records = QualityProbe.score_records
    evaluate = AdaParseEngine.evaluate

    def device_plan_seen(self, prep):
        plan = device_plan(self, prep)
        if not routed:
            out = self._route_step(self.router.enc_params,
                                   prep.route_host["tokens"],
                                   prep.route_host["mask"],
                                   prep.route_host["valid_logit"])
            idx = np.asarray(out["selected_idx"])
            host = scheduler.plan_batch(np.asarray(out["improvement"]),
                                        self.cfg.alpha)
            np.testing.assert_array_equal(np.sort(idx[idx >= 0]),
                                          host.expensive_idx)
            np.testing.assert_array_equal(plan.expensive_idx,
                                          host.expensive_idx)
        routed.append(len(prep.docs))
        return plan

    def score_records_seen(self, docs, records):
        probed.append(len(docs))
        return score_records(self, docs, records)

    def evaluate_seen(self, docs, records):
        evaluated.append(({d.doc_id for d in docs}, set(records)))
        return evaluate(self, docs, records)

    monkeypatch.setattr(AdaParseEngine, "_device_plan", device_plan_seen)
    monkeypatch.setattr(QualityProbe, "score_records", score_records_seen)
    monkeypatch.setattr(AdaParseEngine, "evaluate", evaluate_seen)
    res = serve_main(["--variant", "llm", "--docs", "144",
                      "--batch-size", "32", "--alpha", "0.1",
                      "--adaptive-rounds", "2",
                      "--quality-probe-rate", "0.5"])
    assert routed == [32, 32, 32]
    assert len(evaluated) == 1
    test_ids, record_ids = evaluated[0]
    assert len(test_ids) == 96 and record_ids == test_ids
    assert res["frac_expensive"] <= 0.1 + 1e-9
    assert probed and sum(probed) > 0
    assert all(np.isfinite(v) for v in res.values())


def test_serve_validates_cli_arguments(capsys):
    """Malformed --pools / --prefetch-depth specs exit with an
    actionable argparse error instead of a traceback from deep inside
    ExecutorConfig."""
    import pytest

    from repro.launch.serve import main as serve_main, parse_pools

    assert parse_pools("cpu:2,gpu") == ["cpu", "cpu", "gpu"]
    for spec, frag in [("tpu:2", "unknown pool device"),
                       ("cpu:x", "not an integer"),
                       ("cpu:0", "must be >= 1"),
                       ("cpu:2,,gpu", "empty entry")]:
        with pytest.raises(ValueError, match=frag):
            parse_pools(spec)

    def err_of(argv):
        with pytest.raises(SystemExit) as e:
            serve_main(argv)
        assert e.value.code == 2
        return capsys.readouterr().err

    assert "unknown pool device" in err_of(["--pools", "tpu:4"])
    assert "--prefetch-depth must be >= 0" in err_of(
        ["--prefetch-depth", "-1"])
    assert "--adaptive-rounds must be >= 0" in err_of(
        ["--adaptive-rounds", "-2"])
    assert "--cache-max-bytes only applies" in err_of(
        ["--cache-max-bytes", "1000"])
    assert "--cache-max-bytes must be >= 1" in err_of(
        ["--cache-dir", "/tmp/x", "--cache-max-bytes", "0"])
    assert "--nodes must be >= 1" in err_of(["--nodes", "0"])
    # quality-retune flags: same actionable-error style
    from repro.launch.serve import parse_alpha_bounds

    assert parse_alpha_bounds("0.05:0.4") == (0.05, 0.4)
    for spec, frag in [("0.4", "no ':'"), ("a:b", "not a pair"),
                       ("0.5:0.1", "out of order"),
                       ("-0.1:0.5", "out of order")]:
        with pytest.raises(ValueError, match=frag):
            parse_alpha_bounds(spec)
    assert "--quality-probe-rate must be in [0, 1]" in err_of(
        ["--quality-probe-rate", "1.5"])
    assert "--quality-probe-rate needs --adaptive-rounds" in err_of(
        ["--quality-probe-rate", "0.5"])
    assert "--alpha-step must be > 0" in err_of(["--alpha-step", "0"])
    assert "needs --adaptive-rounds" in err_of(
        ["--alpha-bounds", "0.05:0.4"])
    assert "needs --quality-probe-rate" in err_of(
        ["--alpha-bounds", "0.05:0.4", "--adaptive-rounds", "2"])
    assert "outside --alpha-bounds" in err_of(
        ["--alpha-bounds", "0.1:0.4", "--adaptive-rounds", "2",
         "--quality-probe-rate", "0.5", "--alpha", "0.05"])


def test_serve_driver_quality_retune_flags(capsys):
    """serve --quality-probe-rate/--alpha-bounds: the adaptive run wires
    the probe + retuner into the controller and reports the α
    trajectory; metrics stay sane."""
    from repro.launch.serve import main as serve_main

    res = serve_main(["--docs", "108", "--alpha", "0.05",
                      "--batch-size", "8", "--nodes", "2",
                      "--adaptive-rounds", "3",
                      "--quality-probe-rate", "1.0",
                      "--alpha-bounds", "0.05:0.5",
                      "--alpha-step", "0.2"])
    out = capsys.readouterr().out
    assert "quality probe docs=" in out and "alpha 0.05" in out
    assert res["bleu"] > 0.2
    assert res["frac_expensive"] <= 0.5 + 1e-9


def test_serve_driver_adaptive_disk_cached_restart(tmp_path):
    """serve --adaptive-rounds + --cache-dir: the second invocation (a
    real process restart would hit the same path) replays every batch
    from the disk store and reports identical metrics."""
    from repro.launch.serve import main as serve_main

    argv = ["--docs", "90", "--alpha", "0.1", "--batch-size", "16",
            "--pools", "cpu:2,gpu:1", "--adaptive-rounds", "2",
            "--cache-dir", str(tmp_path / "store")]
    cold = serve_main(argv)
    warm = serve_main(argv)
    assert warm["bleu"] == cold["bleu"]
    assert warm["coverage"] == cold["coverage"]


def test_compressed_allreduce_error_feedback_converges():
    """int8-compressed gradient means with error feedback track the true
    mean over steps (bias -> 0)."""
    from repro.optim.compression import compressed_gradients, \
        init_compression_state
    rng = np.random.RandomState(0)
    g_true = {"w": jnp.asarray(rng.randn(64) * 0.01, jnp.float32)}
    state = init_compression_state(g_true)
    acc = jnp.zeros(64)
    acc_true = jnp.zeros(64)
    for _ in range(50):
        comp, state, _ = compressed_gradients(g_true, state, scheme="int8")
        acc = acc + comp["w"]
        acc_true = acc_true + g_true["w"]
    err = float(jnp.abs(acc - acc_true).max() / jnp.abs(acc_true).max())
    assert err < 0.01


def test_router_cell_route_step_budget():
    """The fused route step selects exactly floor(alpha*B) docs (floor
    semantics: alpha*B < 1 routes nothing — the budget is a hard cap)."""
    from repro.launch.specs import build_cell
    cell = build_cell("adaparse-router", "route_64k", abstract=False,
                      reduced=True)
    out = jax.jit(cell.fn)(*cell.args)
    b = out["improvement"].shape[0]
    assert out["selected_idx"].shape[0] == int(0.05 * b)
    assert out["selected_mask"].sum() <= int(0.05 * b)
    assert out["pred_acc"].shape == (b, 6)
