"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.budget_route import autotune as rt_autotune
from repro.kernels.budget_route.kernel import budget_route_kernel
from repro.kernels.budget_route.ops import budget_route, capacity_floor
from repro.kernels.budget_route.ref import budget_route_ref
from repro.kernels.ngram_score.kernel import ngram_bleu_kernel
from repro.kernels.ngram_score.ref import ngram_bleu_ref
from repro.kernels.embedding_bag.kernel import embedding_bag_kernel
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.segment_mm.kernel import segment_matmul_kernel
from repro.kernels.segment_mm.ref import segment_matmul_ref


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("b,sq,skv,h,hk,d", [
    (2, 64, 64, 4, 2, 16),
    (1, 48, 80, 4, 4, 32),
    (2, 96, 96, 8, 1, 8),       # MQA
    (1, 100, 100, 2, 2, 64),    # padding path
])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 24)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, skv, h, hk, d, causal, window, dtype):
    q = jax.random.normal(jax.random.key(1), (b, sq, h, d), dtype)
    k = jax.random.normal(jax.random.key(2), (b, skv, hk, d), dtype)
    v = jax.random.normal(jax.random.key(3), (b, skv, hk, d), dtype)
    got = flash_attention_kernel(q, k, v, causal=causal, window=window,
                                 block_q=32, block_k=32, interpret=True)
    want = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("n,d,cap,block", [
    (128, 16, 8, 32), (100, 8, 13, 32), (256, 32, 64, 64), (64, 4, 64, 16),
])
def test_budget_route_sweep(n, d, cap, block):
    scores = jax.random.normal(jax.random.key(1), (n,))
    tokens = jax.random.normal(jax.random.key(2), (n, d))
    tau = jax.lax.top_k(scores, min(cap, n))[0][-1]
    o1, i1, c1 = budget_route_kernel(scores, tokens, tau, capacity=cap,
                                     block_n=block, interpret=True)
    o2, i2, c2 = budget_route_ref(scores, tokens, tau, capacity=cap)
    assert int(c1) == int(c2)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2))


@pytest.mark.slow
def test_budget_route_interpret_at_route_64k_shape():
    """The fused selection op at the `route_64k` production serve shape
    (65536 docs x 512 tokens, alpha = 0.05), kernel in interpret mode vs
    the jnp ref AND the host mirror — keeps the kernel path honest at
    the real shape until real-TPU runs land (ROADMAP open item). Scores
    are heavily quantized so the tie budget carries across many grid
    blocks."""
    from repro.configs import get_config
    from repro.core import scheduler

    shape = next(s for s in get_config("adaparse-router").shapes
                 if s.name == "route_64k")
    n, d = shape.dims["global_batch"], shape.dims["seq_len"]
    alpha = 0.05
    cap = int(alpha * n)
    rng = np.random.RandomState(0)
    scores = (rng.randint(0, 50, n) / 10.0).astype(np.float32)
    tokens = rng.randn(n, d).astype(np.float32)
    tau = float(np.sort(scores)[-cap])
    o1, i1, c1 = budget_route_kernel(jnp.asarray(scores),
                                     jnp.asarray(tokens), tau,
                                     capacity=cap, block_n=1024,
                                     interpret=True)
    o2, i2, c2 = budget_route_ref(jnp.asarray(scores), jnp.asarray(tokens),
                                  tau, capacity=cap)
    assert int(c1) == int(c2) == cap
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2))
    # host mirror picks the same document set at the same shape
    host = scheduler.plan_batch(scores, alpha)
    idx = np.asarray(i1)
    np.testing.assert_array_equal(np.sort(idx[idx >= 0]),
                                  host.expensive_idx)


def test_budget_route_selects_topk():
    """Selected rows are exactly the alpha-fraction highest scores."""
    n, cap = 200, 20
    scores = jax.random.normal(jax.random.key(5), (n,))
    tokens = jnp.arange(n, dtype=jnp.float32)[:, None]
    tau = jax.lax.top_k(scores, cap)[0][-1]
    _, idx, count = budget_route_kernel(scores, tokens, tau, capacity=cap,
                                        interpret=True)
    top = set(np.asarray(jax.lax.top_k(scores, cap)[1]).tolist())
    assert int(count) == cap
    assert set(np.asarray(idx).tolist()) == top


# ---------------------------------------------------------------------------
# ngram_score: fused BLEU kernel vs the numpy oracle vs the host scorer
# ---------------------------------------------------------------------------


def _ngram_batch(b, max_len, lens_r, lens_h, vocab=12, seed=0):
    """Padded (B, max_len) batches whose pad region is GARBAGE (not
    zeros) — parity then proves the length masks, not lucky padding."""
    rng = np.random.RandomState(seed)
    ref = rng.randint(1, vocab, (b, max_len)).astype(np.int32)
    hyp = rng.randint(1, vocab, (b, max_len)).astype(np.int32)
    lr = np.asarray(lens_r, np.int32)
    lh = np.asarray(lens_h, np.int32)
    return ref, hyp, lr, lh


def _kernel_vs_ref(ref, hyp, lr, lh, max_n=4):
    got = ngram_bleu_kernel(jnp.asarray(ref), jnp.asarray(hyp),
                            jnp.asarray(lr), jnp.asarray(lh),
                            max_len=ref.shape[1], max_n=max_n,
                            interpret=True)
    want = ngram_bleu_ref(ref, hyp, lr, lh, max_n=max_n)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("b,max_len,vocab", [
    (4, 32, 6),          # tiny vocab -> heavy n-gram repetition (clipping)
    (6, 48, 30),
    (3, 64, 4),          # near-degenerate alphabet
])
def test_ngram_bleu_kernel_vs_ref_sweep(b, max_len, vocab):
    rng = np.random.RandomState(b * 7 + max_len)
    lr = rng.randint(1, max_len + 1, b)
    lh = rng.randint(1, max_len + 1, b)
    ref, hyp, lr, lh = _ngram_batch(b, max_len, lr, lh, vocab=vocab,
                                    seed=max_len)
    _kernel_vs_ref(ref, hyp, lr, lh)


def test_ngram_bleu_kernel_edge_cases():
    """Empty hypotheses, empty references, full-length rows, and rows
    shorter than the n-gram order all agree with the oracle; the empty
    hypothesis scores exactly 0."""
    max_len = 24
    lens_r = [0, 10, max_len, 2, 1, max_len]
    lens_h = [5, 0, max_len, 3, 1, 1]
    ref, hyp, lr, lh = _ngram_batch(6, max_len, lens_r, lens_h, vocab=5)
    _kernel_vs_ref(ref, hyp, lr, lh)
    got = np.asarray(ngram_bleu_kernel(
        jnp.asarray(ref), jnp.asarray(hyp), jnp.asarray(lr),
        jnp.asarray(lh), max_len=max_len, interpret=True))
    assert got[1] == 0.0                 # empty hypothesis


def test_ngram_bleu_padding_is_ignored():
    """Two batches identical inside the lengths but with different
    garbage padding must score identically."""
    lens_r, lens_h = [7, 12], [9, 4]
    ref, hyp, lr, lh = _ngram_batch(2, 16, lens_r, lens_h, seed=1)
    ref2, hyp2 = ref.copy(), hyp.copy()
    rng = np.random.RandomState(99)
    for i in range(2):
        ref2[i, lr[i]:] = rng.randint(1000, 2000, 16 - lr[i])
        hyp2[i, lh[i]:] = rng.randint(1000, 2000, 16 - lh[i])
    a = np.asarray(ngram_bleu_kernel(jnp.asarray(ref), jnp.asarray(hyp),
                                     jnp.asarray(lr), jnp.asarray(lh),
                                     max_len=16, interpret=True))
    b = np.asarray(ngram_bleu_kernel(jnp.asarray(ref2), jnp.asarray(hyp2),
                                     jnp.asarray(lr), jnp.asarray(lh),
                                     max_len=16, interpret=True))
    np.testing.assert_array_equal(a, b)


def test_ngram_bleu_matches_host_scorer():
    """Kernel and oracle both reproduce the scalar host rule
    (metrics.bleu) on unpadded streams — the end-to-end quality-probe
    contract."""
    from repro.core import metrics as M

    rng = np.random.RandomState(3)
    max_len = 40
    refs = [rng.randint(1, 9, rng.randint(1, max_len + 1)).astype(np.int32)
            for _ in range(5)]
    hyps = [rng.randint(1, 9, rng.randint(0, max_len + 1)).astype(np.int32)
            for _ in range(5)]
    ref = np.zeros((5, max_len), np.int32)
    hyp = np.zeros((5, max_len), np.int32)
    for i, (r, h) in enumerate(zip(refs, hyps)):
        ref[i, :len(r)] = r
        hyp[i, :len(h)] = h
    lr = np.asarray([len(r) for r in refs], np.int32)
    lh = np.asarray([len(h) for h in hyps], np.int32)
    want = np.asarray([M.bleu(r, h) for r, h in zip(refs, hyps)])
    np.testing.assert_allclose(ngram_bleu_ref(ref, hyp, lr, lh), want,
                               atol=1e-12)
    got = ngram_bleu_kernel(jnp.asarray(ref), jnp.asarray(hyp),
                            jnp.asarray(lr), jnp.asarray(lh),
                            max_len=max_len, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=1e-6, rtol=1e-5)


# ---------------------------------------------------------------------------
# budget_route block-size autotune
# ---------------------------------------------------------------------------


def test_autotune_caches_winner_and_budget_route_consults_it():
    rt_autotune.clear_cache()
    try:
        n, d, cap = 256, 8, 16
        rec = rt_autotune.autotune_budget_route(
            n, d, cap, candidates=(32, 64, 128), repeats=1)
        assert rec.value in (32, 64, 128)
        assert len(rec.timings_s) == 3
        assert rt_autotune.tuned_block_n(n, d, cap) == rec.value
        # untuned shape falls back to the default
        assert (rt_autotune.tuned_block_n(n + 1, d, cap)
                == rt_autotune.DEFAULT_BLOCK_N)
        # budget_route with block_n=None (the tuned path) still selects
        # the exact same documents as the jnp reference
        rng = np.random.RandomState(0)
        scores = jnp.asarray(rng.rand(n).astype(np.float32))
        tokens = jnp.asarray(rng.randn(n, d).astype(np.float32))
        alpha = cap / n
        o1, i1, c1 = budget_route(scores, tokens, alpha, force_kernel=True)
        kth = jax.lax.top_k(scores, cap)[0][-1]
        o2, i2, c2 = budget_route_ref(scores, tokens, kth, capacity=cap)
        assert int(c1) == int(c2)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2))
    finally:
        rt_autotune.clear_cache()


def test_autotune_device_sweep_refuses_off_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("device sweep is legal on a real TPU")
    with pytest.raises(RuntimeError, match="TPU backend"):
        rt_autotune.autotune_budget_route(64, 4, 4, device=True)


def test_autotune_key_separates_interpret_from_device():
    """Regression for the PR-7 cache key omitting the device flag: an
    interpret-mode winner must never answer a device-mode lookup (on a
    TPU host that would poison compiled dispatch with interpret
    timings), and each mode resolves independently."""
    rt_autotune.clear_cache()
    try:
        n, d, cap = 128, 8, 16
        rec = rt_autotune.autotune_budget_route(
            n, d, cap, candidates=(32, 64), repeats=1)
        assert rec.device is False
        # the interpret winner serves interpret-mode lookups only
        assert rt_autotune.tuned_block_n(n, d, cap, device=False) \
            == rec.value
        assert rt_autotune.tuned_block_n(n, d, cap, device=True) \
            == rt_autotune.DEFAULT_BLOCK_N
        # the store key separates the modes too
        from repro.kernels import autotune_common
        k_int = autotune_common.store_key("budget_route", (n, d, cap),
                                          "cpu", False)
        k_dev = autotune_common.store_key("budget_route", (n, d, cap),
                                          "cpu", True)
        assert k_int != k_dev
    finally:
        rt_autotune.clear_cache()


@pytest.mark.slow
def test_autotune_full_grid_at_route_64k():
    """The full candidate grid at the production route_64k shape in
    interpret mode — every BlockSpec configuration must produce a
    winner and a complete timing table."""
    rt_autotune.clear_cache()
    try:
        n, d = rt_autotune.ROUTE_64K
        cap = max(capacity_floor(0.05, n), 1)
        rec = rt_autotune.autotune_budget_route(
            n, d, cap, candidates=rt_autotune.DEFAULT_CANDIDATES,
            repeats=1)
        grid = sorted({min(c, n) for c in rt_autotune.DEFAULT_CANDIDATES})
        assert [b for b, _ in rec.timings_s] == grid
        assert rec.value in grid
        assert all(t > 0 for _, t in rec.timings_s)
        assert rt_autotune.tuned_block_n(n, d, cap) == rec.value
    finally:
        rt_autotune.clear_cache()


# ---------------------------------------------------------------------------
# ngram_score block_b (docs-per-program) blocking
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_b", [1, 2, 4, 8, 32])
def test_ngram_bleu_block_b_parity(block_b):
    """Every docs-per-program blocking (including one larger than the
    batch, and batch sizes that don't divide the block) scores exactly
    like the unblocked kernel and the oracle."""
    b, max_len = 13, 24
    rng = np.random.RandomState(7)
    lr = rng.randint(0, max_len + 1, b)
    lh = rng.randint(0, max_len + 1, b)
    ref, hyp, lr, lh = _ngram_batch(b, max_len, lr, lh, vocab=6, seed=2)
    got = ngram_bleu_kernel(jnp.asarray(ref), jnp.asarray(hyp),
                            jnp.asarray(lr), jnp.asarray(lh),
                            max_len=max_len, interpret=True,
                            block_b=block_b)
    want = ngram_bleu_ref(ref, hyp, lr, lh)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=1e-6, rtol=1e-5)


def test_ngram_autotune_sweep_and_dispatch():
    """The ngram block_b sweep runs on the shared harness; the public
    op consults the winner and still matches the oracle."""
    from repro.kernels.ngram_score import autotune as ng_autotune
    from repro.kernels.ngram_score.ops import ngram_bleu

    ng_autotune.clear_cache()
    try:
        b, max_len = 9, 16
        rec = ng_autotune.autotune_ngram_bleu(
            b, max_len, candidates=(1, 2, 4), repeats=1)
        assert rec.value in (1, 2, 4)
        assert rec.param == "block_b"
        assert ng_autotune.tuned_block_b(b, max_len) == rec.value
        assert (ng_autotune.tuned_block_b(b + 1, max_len)
                == ng_autotune.DEFAULT_BLOCK_B)
        ref, hyp, lr, lh = _ngram_batch(b, max_len, [5] * b, [7] * b,
                                        vocab=5, seed=3)
        got = ngram_bleu(ref, hyp, lr, lh, force_kernel=True)
        np.testing.assert_allclose(got, ngram_bleu_ref(ref, hyp, lr, lh),
                                   atol=1e-6, rtol=1e-5)
    finally:
        ng_autotune.clear_cache()


# ---------------------------------------------------------------------------
# fast_features: fused prepare-stage kernel vs oracle vs legacy pipeline
# ---------------------------------------------------------------------------


def _page_batch(n, seed, vocab=10000, max_pg_tok=200):
    """Parser-output batches covering the CLS-I edge cases: docs with no
    pages, docs whose pages are all empty, max-length single-page docs,
    and high token ids (the non-ASCII analogue: latex/ident/garbage
    ranges near the top of the vocab)."""
    r = np.random.RandomState(seed)
    out = []
    for i in range(n):
        kind = r.randint(0, 7)
        if kind == 0:
            out.append([])                           # no output at all
        elif kind == 1:
            out.append([np.zeros(0, np.int32)
                        for _ in range(r.randint(1, 4))])   # empty pages
        elif kind == 2:
            out.append([r.randint(vocab - 300, vocab,
                                  max_pg_tok).astype(np.int32)])
        else:
            out.append([r.randint(0, vocab,
                                  r.randint(0, max_pg_tok)).astype(np.int32)
                        for _ in range(r.randint(1, 6))])
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("max_len", [0, 32])
def test_fast_features_ref_matches_legacy_bitwise(seed, max_len):
    """The packed-stream host oracle reproduces the legacy per-function
    pipeline bit-for-bit (it is the CPU dispatch path, so records must
    not move)."""
    from repro.core import features as F
    from repro.data.synthetic import CorpusConfig
    from repro.kernels.fast_features.ops import (pack_routing_batch,
                                                 routing_features)

    cfg = CorpusConfig()
    pls = _page_batch(50, seed, vocab=cfg.vocab_size)
    packed = pack_routing_batch(pls, max_len=max_len)
    fast, toks, mask = routing_features(
        packed, ws=2, scramble=3, mangled=4, latex_lo=cfg.latex_lo,
        ident_lo=cfg.ident_lo, vocab_size=cfg.vocab_size)
    np.testing.assert_array_equal(fast, F.batch_fast_features(pls, cfg))
    if max_len:
        lt, lm = F.batch_first_page_tokens(pls, max_len)
        np.testing.assert_array_equal(toks, lt)
        np.testing.assert_array_equal(mask, lm)
    else:
        assert toks is None and mask is None


@pytest.mark.parametrize("seed,max_len,block_l", [
    (0, 32, 128), (1, 32, 256), (2, 0, 128), (3, 64, 512),
])
def test_fast_features_kernel_vs_ref(seed, max_len, block_l):
    """Pallas kernel (interpret) vs the host oracle to 1e-6 across the
    edge-case corpus: empty docs, empty pages, max-length streams, high
    token ids, every block_l candidate."""
    from repro.data.synthetic import CorpusConfig
    from repro.kernels.fast_features.kernel import fast_features_kernel
    from repro.kernels.fast_features.ops import pack_routing_batch
    from repro.kernels.fast_features.ref import routing_features_ref

    cfg = CorpusConfig()
    pls = _page_batch(40, seed, vocab=cfg.vocab_size)
    packed = pack_routing_batch(pls, max_len=max_len)
    kw = dict(ws=2, scramble=3, mangled=4, latex_lo=cfg.latex_lo,
              ident_lo=cfg.ident_lo)
    want, wt, wm = routing_features_ref(
        packed.flat, packed.rows, packed.starts, packed.n_tok,
        packed.first_len, packed.n_pages, packed.n_empty,
        vocab_size=cfg.vocab_size, max_len=max_len, **kw)
    got, gt, gm = fast_features_kernel(
        jnp.asarray(packed.tok_matrix), jnp.asarray(packed.n_tok),
        jnp.asarray(packed.first_len), jnp.asarray(packed.n_pages),
        jnp.asarray(packed.n_empty), max_len=max_len,
        block_l=min(block_l, packed.width), interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=1e-6, rtol=1e-5)
    if max_len:
        np.testing.assert_array_equal(np.asarray(gt), wt)
        np.testing.assert_array_equal(np.asarray(gm), wm)


def test_fast_features_engine_force_mode_matches_host():
    """prepare_batch in feature_kernel='force' (interpret kernel)
    produces routing inputs matching the host path: tokens/mask exact,
    features to 1e-6."""
    from repro.core import features as F
    from repro.data.synthetic import CorpusConfig

    cfg = CorpusConfig()
    pls = _page_batch(30, 5, vocab=cfg.vocab_size)
    hf, ht, hm = F.prepare_routing_inputs(pls, cfg, max_len=24,
                                          mode="host")
    kf, kt, km = F.prepare_routing_inputs(pls, cfg, max_len=24,
                                          mode="force")
    np.testing.assert_allclose(np.asarray(kf, np.float64), hf, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(kt), ht)
    np.testing.assert_array_equal(np.asarray(km), hm)
    with pytest.raises(ValueError, match="feature_kernel"):
        F.prepare_routing_inputs(pls, cfg, mode="gpu")


@pytest.mark.parametrize("e,n,din,dout", [
    (100, 20, 16, 8), (256, 64, 8, 8), (73, 10, 32, 16),
])
def test_segment_mm_sweep(e, n, din, dout):
    x = jax.random.normal(jax.random.key(0), (n, din))
    src = jax.random.randint(jax.random.key(1), (e,), 0, n)
    dst = jax.random.randint(jax.random.key(2), (e,), 0, n)
    w = jax.random.normal(jax.random.key(3), (din, dout))
    order = jnp.argsort(dst, stable=True)
    xg = jnp.take(x, src[order], axis=0)
    got = segment_matmul_kernel(xg, w, dst[order], n_nodes=n, block_e=64,
                                interpret=True)
    want = segment_matmul_ref(xg, w, dst[order], n_nodes=n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("r,d,b,bag,comb", [
    (500, 16, 32, 8, "sum"), (1000, 8, 50, 5, "mean"), (64, 4, 7, 3, "sum"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_sweep(r, d, b, bag, comb, dtype):
    table = jax.random.normal(jax.random.key(0), (r, d), dtype)
    ids = jax.random.randint(jax.random.key(1), (b, bag), 0, r)
    w = jax.random.uniform(jax.random.key(2), (b, bag))
    got = embedding_bag_kernel(table, ids, w, combiner=comb, interpret=True)
    want = embedding_bag_ref(table, ids, w, combiner=comb)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# f32math: the kernels' float32-accurate log / exp vs float64 numpy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fn,xs", [
    ("log", np.arange(1, 300001, dtype=np.float64)),       # length feature
    ("log", np.geomspace(1e-12, 1.0, 200001)),             # n-gram precisions
    ("exp", np.linspace(-80.0, 1.0, 200001)),              # BLEU, brevity
])
def test_f32math_matches_float64(fn, xs):
    from repro.kernels import f32math

    x32 = xs.astype(np.float32)
    got = np.asarray(jax.jit(getattr(f32math, fn))(jnp.asarray(x32)),
                     np.float64)
    want = getattr(np, fn)(x32.astype(np.float64))
    rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
    assert rel.max() < 3e-7          # a couple of float32 ulps
