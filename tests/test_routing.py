"""Routing parity: the host numpy mirror (scheduler.plan_batch), the jnp
reference, and the Pallas kernel (interpret mode) must choose identical
document sets on the same scores — plus the budget_topk invariants ported
from the hypothesis suite (seeded, always run in tier-1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scheduler
from repro.kernels.budget_route.kernel import budget_route_kernel
from repro.kernels.budget_route.ops import budget_route
from repro.kernels.budget_route.ref import budget_route_ref


def _device_set(idx) -> set:
    idx = np.asarray(idx)
    return set(idx[idx >= 0].tolist())


# -- budget_topk invariants (ported from tests/test_properties.py) -----------


@pytest.mark.parametrize("k,alpha,seed", [
    (8, 0.0, 0), (8, 1.0, 1), (17, 0.05, 2), (64, 0.1, 3), (100, 0.5, 4),
    (200, 0.031, 5), (33, 0.99, 6), (150, 0.2, 7),
])
def test_budget_topk_respects_budget(k, alpha, seed):
    """Never route more than floor(alpha*k) items; all routed items have
    positive predicted improvement."""
    rng = np.random.RandomState(seed)
    scores = jnp.asarray(rng.randn(k).astype(np.float32))
    mask, idx = scheduler.budget_topk(scores, alpha)
    n_sel = int(mask.sum())
    assert n_sel <= int(alpha * k)
    if n_sel:
        assert float(scores[mask].min()) > 0


@pytest.mark.parametrize("k,alpha,seed", [
    (8, 0.25, 10), (50, 0.04, 11), (64, 0.5, 12), (128, 0.05, 13),
    (99, 0.33, 14), (200, 0.9, 15),
])
def test_budget_topk_takes_the_best(k, alpha, seed):
    """Every selected score >= every unselected score."""
    rng = np.random.RandomState(seed)
    scores = jnp.asarray(rng.randn(k).astype(np.float32))
    mask, _ = scheduler.budget_topk(scores, alpha)
    m = np.asarray(mask)
    if m.any() and (~m).any():
        assert float(scores[m].min()) >= float(scores[~m].max()) - 1e-6


# -- host / ref / kernel three-way agreement ---------------------------------


@pytest.mark.parametrize("k,alpha,seed", [
    (64, 0.05, 0), (64, 0.25, 1), (100, 0.1, 2), (256, 0.05, 3),
    (40, 0.5, 4), (96, 0.031, 5), (128, 1.0, 6),
])
def test_plan_batch_matches_device_selection(k, alpha, seed):
    """Host plan_batch and the fused device op (ref AND Pallas kernel in
    interpret mode) choose identical document sets on the same scores."""
    rng = np.random.RandomState(seed)
    scores = rng.randn(k).astype(np.float32)
    tokens = rng.randn(k, 8).astype(np.float32)
    host = set(scheduler.plan_batch(scores, alpha).expensive_idx.tolist())

    _, idx_ref, cnt_ref = budget_route(jnp.asarray(scores),
                                       jnp.asarray(tokens), alpha)
    _, idx_kern, cnt_kern = budget_route(jnp.asarray(scores),
                                         jnp.asarray(tokens), alpha,
                                         force_kernel=True)
    assert _device_set(idx_ref) == host
    assert _device_set(idx_kern) == host
    assert int(cnt_ref) == int(cnt_kern) == len(host)


def test_parity_alpha_k_zero():
    """alpha*k < 1 routes nothing on both paths (floor semantics — the
    budget is a hard cap)."""
    scores = np.random.RandomState(0).randn(12).astype(np.float32)
    plan = scheduler.plan_batch(scores, 0.05)
    assert plan.expensive_idx.size == 0
    routed, idx, count = budget_route(jnp.asarray(scores),
                                      jnp.zeros((12, 4)), 0.05)
    assert routed.shape == (0, 4) and idx.shape == (0,) and int(count) == 0


def test_parity_all_negative_improvements():
    """No doc with non-positive predicted improvement is ever routed."""
    scores = -np.abs(np.random.RandomState(1).randn(48)).astype(np.float32)
    plan = scheduler.plan_batch(scores, 0.25)
    assert plan.expensive_idx.size == 0
    for fk in (False, True):
        _, idx, count = budget_route(jnp.asarray(scores), jnp.zeros((48, 4)),
                                     0.25, force_kernel=fk)
        assert int(count) == 0 and _device_set(idx) == set()


def test_parity_inf_cls1_overrides():
    """+inf CLS-I overrides (host) / CLS1_OVERRIDE (device) win the budget
    and both paths keep the same ties-in-row-order subset when overrides
    exceed capacity."""
    from repro.core.router import CLS1_OVERRIDE
    k, alpha = 40, 0.1                    # capacity 4, 6 overridden docs
    rng = np.random.RandomState(2)
    scores = rng.randn(k).astype(np.float32) * 0.1
    invalid = np.array([3, 7, 11, 19, 23, 31])
    host_scores = scores.copy()
    host_scores[invalid] = np.inf
    host_scores = np.nan_to_num(host_scores,
                                posinf=CLS1_OVERRIDE).astype(np.float32)
    plan = scheduler.plan_batch(host_scores, alpha)
    assert set(plan.expensive_idx.tolist()) == {3, 7, 11, 19}
    for fk in (False, True):
        _, idx, _ = budget_route(jnp.asarray(host_scores),
                                 jnp.zeros((k, 4)), alpha, force_kernel=fk)
        assert _device_set(idx) == set(plan.expensive_idx.tolist())


def test_parity_capacity_clamp_at_k():
    """alpha = 1: capacity clamps at k; only positive scores routed, and
    host/ref/kernel agree."""
    rng = np.random.RandomState(3)
    scores = rng.randn(32).astype(np.float32)
    plan = scheduler.plan_batch(scores, 1.0)
    want = set(np.nonzero(scores >= scheduler.POSITIVE_TAU)[0].tolist())
    assert set(plan.expensive_idx.tolist()) == want
    for fk in (False, True):
        _, idx, count = budget_route(jnp.asarray(scores),
                                     jnp.zeros((32, 4)), 1.0,
                                     force_kernel=fk)
        assert _device_set(idx) == want and int(count) == len(want)


def test_route_step_device_vs_host_mirror():
    """The full fused route_step (encoder fwd + budget_route) selects
    exactly the set the host mirror picks from the very same improvement
    scores it computed."""
    from repro.common import unwrap
    from repro.configs.base import EncoderConfig
    from repro.core.router import make_route_step
    from repro.models import encoder as enc_lib

    cfg = EncoderConfig(name="t", n_layers=1, d_model=16, n_heads=2,
                        d_ff=32, vocab_size=64, max_len=12,
                        param_dtype="float32", compute_dtype="float32")
    params = unwrap(enc_lib.init_encoder(cfg, 0))
    rng = np.random.RandomState(0)
    b = 40
    toks = rng.randint(2, 64, (b, 12)).astype(np.int32)
    mask = np.ones((b, 12), np.float32)
    valid = rng.randn(b).astype(np.float32)
    step = jax.jit(make_route_step(cfg, alpha=0.1))
    out = step(params, jnp.asarray(toks), jnp.asarray(mask),
               jnp.asarray(valid))
    imp = np.asarray(out["improvement"]).astype(np.float32)
    host = set(scheduler.plan_batch(imp, 0.1).expensive_idx.tolist())
    assert _device_set(out["selected_idx"]) == host
    assert set(np.nonzero(np.asarray(out["selected_mask"]))[0].tolist()) \
        == host
    # invalid docs carry the CLS-I override score
    from repro.core.router import CLS1_OVERRIDE
    assert (imp[valid < 0] == CLS1_OVERRIDE).all()


def test_route_step_attention_kernel_matches_einsum_path():
    """The route step with the encoder attention kernel forced (interpret
    mode here) selects exactly what the einsum path selects, and its
    predicted accuracies agree within bf16 rounding."""
    from repro.common import unwrap
    from repro.configs.base import EncoderConfig
    from repro.core.router import make_route_step
    from repro.models import encoder as enc_lib

    cfg = EncoderConfig(name="t", n_layers=2, d_model=32, n_heads=2,
                        d_ff=64, vocab_size=64, max_len=16,
                        param_dtype="float32", compute_dtype="bfloat16")
    params = unwrap(enc_lib.init_encoder(cfg, 0))
    rng = np.random.RandomState(0)
    b = 40
    toks = jnp.asarray(rng.randint(2, 64, (b, 16)).astype(np.int32))
    mask = np.ones((b, 16), np.float32)
    mask[::3, 9:] = 0                         # a third of the rows padded
    mask[1, 1:] = 0                           # one row of BOS alone
    valid = jnp.ones((b,), jnp.float32)       # the encoder ranks every row
    # centre the head so half the rows gain from the expensive parser
    # (index 2 over index 0): the top-4 is then a real ranking
    pooled = enc_lib.encode(params, cfg, toks, jnp.asarray(mask))
    z = np.asarray(pooled.astype(jnp.float32) @ params["head_w"])
    params["head_b"] = params["head_b"].at[2].add(
        float(np.median(z[:, 0] - z[:, 2])))
    einsum_step = make_route_step(cfg, alpha=0.1)
    kernel_step = make_route_step(cfg, alpha=0.1, force_kernel=True)
    assert kernel_step.attention_kernel and not einsum_step.attention_kernel
    want = jax.jit(einsum_step)(params, toks, jnp.asarray(mask), valid)
    got = jax.jit(kernel_step)(params, toks, jnp.asarray(mask), valid)
    assert int(want["count"]) == 4 and \
        np.sum(np.asarray(want["improvement"]) > 0) > 8
    np.testing.assert_array_equal(np.asarray(got["selected_idx"]),
                                  np.asarray(want["selected_idx"]))
    assert int(got["count"]) == int(want["count"])
    np.testing.assert_allclose(np.asarray(got["pred_acc"]),
                               np.asarray(want["pred_acc"]),
                               atol=1e-2, rtol=0)


def test_ties_never_displace_strictly_better():
    """A strictly higher-scoring doc is always routed, even when tied
    lower scores fill the batch ahead of it in row order (host, ref, and
    kernel all guarantee rows > tau are kept; only ties at tau compete
    for the remaining slots)."""
    scores = np.array([0.3, 0.3, 0.7], np.float32)   # capacity 2
    plan = scheduler.plan_batch(scores, 2 / 3)
    assert 2 in plan.expensive_idx.tolist()
    assert set(plan.expensive_idx.tolist()) == {0, 2}
    for fk in (False, True):
        _, idx, count = budget_route(jnp.asarray(scores),
                                     jnp.zeros((3, 4)), 2 / 3,
                                     force_kernel=fk)
        assert _device_set(idx) == {0, 2} and int(count) == 2
    # many ties before the best doc, tie budget spread across blocks
    scores = np.full(80, 0.5, np.float32)
    scores[70] = 2.0
    plan = scheduler.plan_batch(scores, 0.1)          # capacity 8
    assert plan.expensive_idx.tolist() == [0, 1, 2, 3, 4, 5, 6, 70]
    for fk in (False, True):
        _, idx, _ = budget_route(jnp.asarray(scores), jnp.zeros((80, 4)),
                                 0.1, force_kernel=fk)
        assert _device_set(idx) == set(plan.expensive_idx.tolist())
    # small blocks: the tie budget must carry across kernel grid steps
    _, idx, _ = budget_route_kernel(jnp.asarray(scores),
                                    jnp.zeros((80, 4)), 0.5, capacity=8,
                                    block_n=16, interpret=True)
    assert _device_set(idx) == set(plan.expensive_idx.tolist())


@pytest.mark.parametrize("k,alpha,want", [
    # rational α whose product is an exact integer: IEEE gives
    # 28.999999999999996 and int() under-floors to 28 — the float-dust
    # capacity bug this sweep regresses
    (100, 0.29, 29), (50, 0.58, 29), (200, 0.145, 29),
    # exact and near-exact products that must stay unchanged
    (10, 0.7, 7), (3, 2 / 3, 2), (300, 0.07, 21),
    # genuinely fractional products must still truncate, never snap up
    (100, 0.2899999, 28),
])
def test_capacity_floor_rational_alpha_parity(k, alpha, want):
    """⌊α·k⌋ is exact for rational α across every selection path —
    the shared epsilon-guarded floor — and host mirror, jnp ref, and
    Pallas kernel (interpret) agree on the selected set."""
    from repro.kernels.budget_route.ops import capacity_floor

    assert capacity_floor(alpha, k) == want
    rng = np.random.RandomState(k)
    # all-positive scores so capacity alone determines the count
    scores = (np.abs(rng.randn(k)) + 1.0).astype(np.float32)
    plan = scheduler.plan_batch(scores, alpha)
    assert plan.expensive_idx.size == want
    mask, _ = scheduler.budget_topk(jnp.asarray(scores), alpha)
    assert int(np.asarray(mask).sum()) == want
    tokens = rng.randn(k, 4).astype(np.float32)
    for fk in (False, True):
        _, idx, count = budget_route(jnp.asarray(scores),
                                     jnp.asarray(tokens), alpha,
                                     force_kernel=fk)
        assert int(count) == want
        assert _device_set(idx) == set(plan.expensive_idx.tolist())


@pytest.mark.parametrize("n,cap", [(64, 7), (100, 100), (128, 1)])
def test_kernel_vs_ref_tie_handling(n, cap):
    """Duplicate scores at the threshold: kernel and ref both keep the
    earliest rows (stable compaction)."""
    rng = np.random.RandomState(4)
    scores = rng.randint(0, 5, n).astype(np.float32)   # heavy ties
    tokens = rng.randn(n, 4).astype(np.float32)
    tau = float(np.sort(scores)[-cap])
    o1, i1, c1 = budget_route_kernel(scores, tokens, tau, capacity=cap,
                                     interpret=True)
    o2, i2, c2 = budget_route_ref(jnp.asarray(scores), jnp.asarray(tokens),
                                  tau, capacity=cap)
    assert int(c1) == int(c2)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2))
