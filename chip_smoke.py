"""Smoke run of the parsing campaign's main path on one TPU chip.

    python chip_smoke.py

Run from the root of a checkout on a machine with a TPU. One process,
one chip, four phases; any failure raises and exits non-zero:

(a) device: JAX's default device must be a TPU. There is no CPU
    fallback: on any other backend the script exits 1 and prints no
    result.
(b) kernels: the three main-path Pallas kernels, compiled for the chip,
    against their host oracles at the campaign's shapes —
    ``budget_route`` at the route batch (256 x 512, k=256) and at
    ``route_64k`` (65536 x 512): identical selected sets, rows and
    counts; ``fast_features`` on a prepared 256-document batch: tokens
    and mask exact, features to 1e-6; ``ngram_score`` on a padded
    256 x 256 probe batch against the float64 oracle to 1e-5 relative
    (+1e-6 absolute), float32 rounding of the log/exp chain.
(c) campaign: ``serve.main`` with the llm variant and the published
    ``adaparse-router`` encoder (12 layers, d=768, 512 tokens, seeded
    weights): SFT, DPO and refit steps at full width, then 768 test
    documents in 3 route batches of k=256 through the jitted route step
    (encoder + budget_route) with the fast_features prepare stage and
    the ngram_score quality probe (rate 0.5: at seed 0 it samples all 3
    batches, where 0.25 samples none). Checks: finite metrics, one
    record per test document, expensive share <= alpha, probed batches,
    and one batch's
    ``selected_idx`` equal to ``scheduler.plan_batch`` on that route
    step's own ``improvement`` output.
(d) output: compile and wall seconds and parity lines (a smoke, not a
    benchmark), then one JSON line
    ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Only committed files are used: no tuning store, result store or trace
directory is passed, and the compile cache follows
``repro.device.ensure_compile_cache``.

``tests/test_chip_smoke.py`` rehearses every phase on the CPU at a tiny
size (kernels in interpret mode, the reduced router), so a change that
breaks the smoke fails there before it costs chip time.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
ALPHA = 0.05
ROUTE_BATCH = 256
CAMPAIGN_ARGV = ["--variant", "llm", "--router-config", "published",
                 "--docs", "1152", "--alpha", str(ALPHA),
                 "--adaptive-rounds", "2", "--quality-probe-rate", "0.5"]


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"smoke check failed: {what}")


def compiled(fn, *args, **static):
    """Lower and compile a jitted kernel; returns (executable, seconds)."""
    t0 = time.perf_counter()
    exe = fn.lower(*args, **static).compile()
    return exe, time.perf_counter() - t0


def timed(exe, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    return out, time.perf_counter() - t0


def budget_route_parity(n: int, d: int, *, interpret: bool = False) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import scheduler
    from repro.kernels.budget_route.kernel import budget_route_kernel
    from repro.kernels.budget_route.ops import capacity_floor

    rng = np.random.RandomState(SEED)
    # quantized scores: ties at tau straddle blocks, exercising the tie
    # budget; all positive, so the host mirror's positive-tau clamp is moot
    scores = (rng.randint(1, 50, n) / 10.0).astype(np.float32)
    tokens = rng.randint(0, 31090, (n, d)).astype(np.int32)
    cap = capacity_floor(ALPHA, n)
    tau = np.float32(np.sort(scores)[-cap])
    args = (jnp.asarray(scores), jnp.asarray(tokens), jnp.asarray(tau))
    exe, t_c = compiled(budget_route_kernel, *args, capacity=cap,
                        interpret=interpret)
    (routed, idx, count), t_r = timed(exe, *args)
    idx = np.asarray(idx)
    want = scheduler.plan_batch(scores, ALPHA).expensive_idx
    check(int(count) == len(want) == cap, f"budget_route count at {n}x{d}")
    check(np.array_equal(np.sort(idx[idx >= 0]), want),
          f"budget_route selected set at {n}x{d}")
    check(np.array_equal(np.asarray(routed), tokens[idx]),
          f"budget_route routed rows at {n}x{d}")
    log(f"budget_route {n}x{d} cap={cap}: compile_s={t_c:.3f} "
        f"run_s={t_r:.4f} selected set == scheduler.plan_batch, "
        f"routed rows exact")


def _probe_batch(n: int):
    """A prepared batch: ``n`` synthetic documents through the cheap
    parser channel."""
    import numpy as np

    from repro.core import parsers as P
    from repro.data.synthetic import CorpusConfig, generate_corpus

    ccfg = CorpusConfig(n_docs=n, seed=SEED)
    docs = generate_corpus(ccfg)
    pages = P.run_parser_batch(P.CHEAP_PARSER, docs, ccfg,
                               np.random.RandomState(SEED + 1))
    return ccfg, docs, pages


def fast_features_parity(n: int, max_len: int, *,
                         interpret: bool = False) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import MANGLED, SCRAMBLE, WS
    from repro.kernels.fast_features.autotune import DEFAULT_BLOCK_L
    from repro.kernels.fast_features.kernel import fast_features_kernel
    from repro.kernels.fast_features.ops import pack_routing_batch
    from repro.kernels.fast_features.ref import routing_features_ref

    ccfg, _, pages = _probe_batch(n)
    packed = pack_routing_batch(pages, max_len=max_len)
    kw = dict(ws=WS, scramble=SCRAMBLE, mangled=MANGLED,
              latex_lo=ccfg.latex_lo, ident_lo=ccfg.ident_lo)
    args = tuple(jnp.asarray(a) for a in (
        packed.tok_matrix, packed.n_tok, packed.first_len, packed.n_pages,
        packed.n_empty))
    exe, t_c = compiled(fast_features_kernel, *args, max_len=max_len,
                        block_l=DEFAULT_BLOCK_L, interpret=interpret, **kw)
    (fast, toks, mask), t_r = timed(exe, *args)
    want, wt, wm = routing_features_ref(
        packed.flat, packed.rows, packed.starts, packed.n_tok,
        packed.first_len, packed.n_pages, packed.n_empty,
        vocab_size=ccfg.vocab_size, max_len=max_len, **kw)
    err = float(np.max(np.abs(np.asarray(fast, np.float64) - want)))
    log(f"fast_features n={n} width={packed.width} max_len={max_len}: "
        f"compile_s={t_c:.3f} run_s={t_r:.4f} features max_abs_err="
        f"{err:.3g} per feature {np.abs(np.asarray(fast) - want).max(0)}")
    check(err <= 1e-6, f"fast_features features max err {err:g} > 1e-6")
    check(np.array_equal(np.asarray(toks), wt), "fast_features tokens")
    check(np.array_equal(np.asarray(mask), wm), "fast_features mask")
    log("fast_features: features within 1e-6, tokens and mask exact")


def ngram_score_parity(n: int, max_len: int, *,
                       interpret: bool = False) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.metrics import _pad_batch
    from repro.kernels.ngram_score.kernel import ngram_bleu_kernel
    from repro.kernels.ngram_score.ref import ngram_bleu_ref

    _, docs, pages = _probe_batch(n)
    refs = [d.full_text() for d in docs]
    hyps = [np.concatenate(p) if sum(map(len, p)) else np.zeros(0, np.int32)
            for p in pages]
    ra, rl = _pad_batch(refs, max_len)
    ha, hl = _pad_batch(hyps, max_len)
    args = tuple(jnp.asarray(a) for a in (ra, ha, rl, hl))
    exe, t_c = compiled(ngram_bleu_kernel, *args, max_len=max_len,
                        interpret=interpret)
    got, t_r = timed(exe, *args)
    want = ngram_bleu_ref(ra, ha, rl, hl)
    got = np.asarray(got, np.float64)
    err = float(np.max(np.abs(got - want)))
    rel = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-12)))
    log(f"ngram_score {n}x{max_len}: compile_s={t_c:.3f} run_s={t_r:.4f} "
        f"max_abs_err={err:.3g} max_rel_err={rel:.3g} vs float64 oracle")
    check(np.allclose(got, want, rtol=1e-5, atol=1e-6),
          f"ngram_score vs float64 oracle: max err {err:g}")
    log("ngram_score: within rtol 1e-5, atol 1e-6 of the float64 oracle")


def campaign(argv, *, route_batch: int = ROUTE_BATCH,
             n_batches: int = 3, published: bool = True) -> None:
    """``serve.main(argv)`` with observation wrappers around the router
    post-training, the engine's device route plan, the quality probe and
    the evaluation."""
    import numpy as np

    from repro.core import dpo, scheduler
    from repro.core.engine import AdaParseEngine
    from repro.core.quality import QualityProbe
    from repro.kernels.budget_route.ops import capacity_floor
    from repro.launch import serve

    trained, routed, probed, evaluated = [], [], [], []
    posttrain = dpo.three_stage_posttrain
    device_plan = AdaParseEngine._device_plan
    score_records = QualityProbe.score_records
    evaluate = AdaParseEngine.evaluate

    def posttrain_seen(params, cfg, *a, **k):
        t0 = time.perf_counter()
        out = posttrain(params, cfg, *a, **k)
        trained.append((cfg, out[1], time.perf_counter() - t0))
        return out

    def device_plan_seen(self, prep):
        t0 = time.perf_counter()
        plan = device_plan(self, prep)
        routed.append((self, prep, plan, time.perf_counter() - t0))
        return plan

    def score_records_seen(self, docs, records):
        probed.append(len(docs))
        return score_records(self, docs, records)

    def evaluate_seen(self, docs, records):
        evaluated.append(({d.doc_id for d in docs}, set(records)))
        return evaluate(self, docs, records)

    dpo.three_stage_posttrain = posttrain_seen
    AdaParseEngine._device_plan = device_plan_seen
    QualityProbe.score_records = score_records_seen
    AdaParseEngine.evaluate = evaluate_seen
    t0 = time.perf_counter()
    try:
        res = serve.main(argv)
    finally:
        dpo.three_stage_posttrain = posttrain
        AdaParseEngine._device_plan = device_plan
        QualityProbe.score_records = score_records
        AdaParseEngine.evaluate = evaluate
    wall = time.perf_counter() - t0

    check(len(trained) == 1, "one router post-training")
    cfg, diag, t_train = trained[0]
    if published:
        check((cfg.n_layers, cfg.d_model, cfg.max_len) == (12, 768, 512),
              f"published router widths, got {cfg}")
    for stage, vals in diag.items():
        check(len(vals) > 0 and all(map(math.isfinite, vals)),
              f"{stage} finite")
    log(f"router {cfg.name}: layers={cfg.n_layers} d={cfg.d_model} "
        f"max_len={cfg.max_len} " + " ".join(
            f"{k}={len(v)} steps {v[0]:.4f}->{v[-1]:.4f}"
            for k, v in diag.items())
        + f" posttrain_wall_s={t_train:.1f}")

    check(len(routed) == n_batches,
          f"{n_batches} route batches, got {len(routed)}")
    for _, prep, plan, t in routed:
        check(len(prep.docs) == route_batch,
              f"route batch of {route_batch}, got {len(prep.docs)}")
    log("route batches: " + " ".join(
        f"k={len(p.docs)} sel={len(pl.expensive_idx)} s={t:.3f}"
        for _, p, pl, t in routed) + " (first includes compile)")
    eng, prep, plan, _ = routed[0]
    out = eng._route_step(eng.router.enc_params, prep.route_host["tokens"],
                          prep.route_host["mask"],
                          prep.route_host["valid_logit"])
    idx = np.asarray(out["selected_idx"])
    host = scheduler.plan_batch(np.asarray(out["improvement"]),
                                eng.cfg.alpha)
    check(np.array_equal(np.sort(idx[idx >= 0]), host.expensive_idx),
          "route step selected_idx == scheduler.plan_batch(improvement)")
    check(np.array_equal(plan.expensive_idx, host.expensive_idx),
          "campaign plan == scheduler.plan_batch(improvement)")
    log(f"route step batch 0: selected_idx == scheduler.plan_batch on its "
        f"own improvement ({len(host.expensive_idx)} docs)")
    # a router that predicts no positive improvement routes nothing; mark
    # the first cap + 3 documents CLS-I invalid so the same compiled step
    # must select, with ties at the override score crossing the capacity
    cap = capacity_floor(eng.cfg.alpha, len(prep.docs))
    forced = np.array(prep.route_host["valid_logit"], np.float32)
    forced[:cap + 3] = -1.0
    out = eng._route_step(eng.router.enc_params, prep.route_host["tokens"],
                          prep.route_host["mask"], forced)
    idx = np.asarray(out["selected_idx"])
    host = scheduler.plan_batch(np.asarray(out["improvement"]),
                                eng.cfg.alpha)
    check(len(host.expensive_idx) == cap == int(out["count"]),
          "forced overrides fill the capacity")
    check(np.array_equal(np.sort(idx[idx >= 0]), host.expensive_idx),
          "forced route step selected_idx == scheduler.plan_batch")
    check(np.array_equal(np.asarray(out["routed_tokens"]),
                         np.asarray(prep.route_host["tokens"])[idx]),
          "forced route step routed rows")
    log(f"route step batch 0 with {cap + 3} forced CLS-I overrides: "
        f"selected_idx == scheduler.plan_batch ({cap} docs), routed rows "
        f"exact")

    check(len(probed) > 0, "the quality probe scored a batch")
    log(f"quality probe: {len(probed)} batches, {sum(probed)} docs scored")
    check(len(evaluated) == 1 and evaluated[0][0] == evaluated[0][1],
          "one record per test document")
    check(all(math.isfinite(v) for v in res.values()), "finite metrics")
    check(res["frac_expensive"] <= eng.cfg.alpha + 1e-9,
          f"expensive share {res['frac_expensive']} <= alpha")
    log(f"campaign: {len(evaluated[0][1])} records for "
        f"{len(evaluated[0][0])} test docs, frac_expensive="
        f"{res['frac_expensive']:.4f} (alpha {eng.cfg.alpha}), "
        f"bleu={res['bleu']:.4f}, wall_s={wall:.1f}")


def main() -> int:
    import jax

    from repro.device import ensure_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's default device is {dev.platform!r}, not "
              f"a TPU; this smoke runs on the chip only", file=sys.stderr)
        return 1
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"compile cache {ensure_compile_cache()}")
    t0 = time.perf_counter()
    budget_route_parity(ROUTE_BATCH, 512)
    budget_route_parity(65536, 512)
    fast_features_parity(ROUTE_BATCH, 512)
    ngram_score_parity(ROUTE_BATCH, 256)
    log(f"kernels wall_s={time.perf_counter() - t0:.1f}")
    campaign(CAMPAIGN_ARGV)
    log(f"total wall_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
