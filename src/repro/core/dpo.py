"""Three-stage DPO post-training (Appendix A).

Stage 1 (SFT): minimize L_REG = E ||pi_theta(x^1) - y||^2 — the encoder
regresses the m per-parser accuracies from the default parser's first-page
text.

Stage 2 (DPO): the encoder is reused inside a scorer g_phi (encoder +
positive scalar head) with a frozen reference copy g_ref; minimize

  L_DPO = -E log sigma( beta * [ log g(x+) - log g_ref(x+)
                               - log g(x-) + log g_ref(x-) ] )

over preference pairs (x+, x-) of parser outputs for the same page.

Stage 3: re-fit the regression head at a lowered learning rate on D.

All stages run on the same Param tree; ``fit`` loops are jit-stepped with
the repro.optim stack.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import unwrap
from repro.configs.base import EncoderConfig
from repro.models import encoder as enc_lib
from repro.optim import adamw, apply_updates, chain_clip


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def dpo_loss(params_raw, ref_params_raw, cfg: EncoderConfig, batch: dict,
             beta: float = 1.0) -> jax.Array:
    """batch: tok_pos/mask_pos and tok_neg/mask_neg (B, S)."""
    g_pos = enc_lib.preference_score(params_raw, cfg, batch["tok_pos"],
                                     batch["mask_pos"])
    g_neg = enc_lib.preference_score(params_raw, cfg, batch["tok_neg"],
                                     batch["mask_neg"])
    r_pos = enc_lib.preference_score(ref_params_raw, cfg, batch["tok_pos"],
                                     batch["mask_pos"])
    r_neg = enc_lib.preference_score(ref_params_raw, cfg, batch["tok_neg"],
                                     batch["mask_neg"])
    logits = beta * (jnp.log(g_pos) - jnp.log(r_pos)
                     - jnp.log(g_neg) + jnp.log(r_neg))
    return -jnp.mean(jax.nn.log_sigmoid(logits))


def pref_accuracy(params_raw, cfg, batch) -> jax.Array:
    g_pos = enc_lib.preference_score(params_raw, cfg, batch["tok_pos"],
                                     batch["mask_pos"])
    g_neg = enc_lib.preference_score(params_raw, cfg, batch["tok_neg"],
                                     batch["mask_neg"])
    return jnp.mean((g_pos > g_neg).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Trainers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FitResult:
    params_raw: dict
    losses: list[float]


def _batches(n, bs, rng):
    idx = rng.permutation(n)
    for i in range(0, n - bs + 1, bs):
        yield idx[i:i + bs]


def fit_regression(params_raw, cfg: EncoderConfig, data: dict,
                   steps: int = 200, lr: float = 1e-3, bs: int = 16,
                   seed: int = 0) -> FitResult:
    """Stage 1 / Stage 3. data: tokens (N,S), mask (N,S), targets (N,m),
    optional target_mask."""
    opt = chain_clip(adamw(lr, weight_decay=0.01), 1.0)
    state = opt.init(params_raw)

    @jax.jit
    def step_fn(params, state, step, batch):
        loss, grads = jax.value_and_grad(
            lambda p: enc_lib.regression_loss(p, cfg, batch))(params)
        updates, state = opt.update(grads, state, params, step)
        return apply_updates(params, updates), state, loss

    rng = np.random.RandomState(seed)
    n = data["tokens"].shape[0]
    losses = []
    it = 0
    while it < steps:
        for bidx in _batches(n, min(bs, n), rng):
            if it >= steps:
                break
            batch = {k: jnp.asarray(v[bidx]) for k, v in data.items()}
            params_raw, state, loss = step_fn(params_raw, state,
                                              jnp.asarray(it), batch)
            losses.append(float(loss))
            it += 1
    return FitResult(params_raw, losses)


def fit_dpo(params_raw, cfg: EncoderConfig, pref_data: dict,
            steps: int = 100, lr: float = 5e-4, bs: int = 8,
            beta: float = 1.0, seed: int = 0) -> FitResult:
    """Stage 2. pref_data: tok_pos/mask_pos/tok_neg/mask_neg (M, S)."""
    ref_params = jax.tree_util.tree_map(lambda x: x, params_raw)  # frozen copy
    opt = chain_clip(adamw(lr, weight_decay=0.0), 1.0)
    state = opt.init(params_raw)

    # the frozen reference is an argument, not a closure: a captured
    # tree would be baked into the executable as constants
    @jax.jit
    def step_fn(params, ref, state, step, batch):
        loss, grads = jax.value_and_grad(
            lambda p: dpo_loss(p, ref, cfg, batch, beta))(params)
        updates, state = opt.update(grads, state, params, step)
        return apply_updates(params, updates), state, loss

    rng = np.random.RandomState(seed)
    n = pref_data["tok_pos"].shape[0]
    losses = []
    it = 0
    while it < steps:
        for bidx in _batches(n, min(bs, n), rng):
            if it >= steps:
                break
            batch = {k: jnp.asarray(v[bidx]) for k, v in pref_data.items()}
            params_raw, state, loss = step_fn(params_raw, ref_params, state,
                                              jnp.asarray(it), batch)
            losses.append(float(loss))
            it += 1
    return FitResult(params_raw, losses)


def three_stage_posttrain(params_raw, cfg: EncoderConfig, reg_data: dict,
                          pref_data: dict, *, sft_steps=200, dpo_steps=100,
                          refit_steps=60, lr=1e-3, seed=0):
    """The full Appendix-A recipe. Returns (params, diagnostics)."""
    r1 = fit_regression(params_raw, cfg, reg_data, steps=sft_steps, lr=lr,
                        seed=seed)
    r2 = fit_dpo(r1.params_raw, cfg, pref_data, steps=dpo_steps, lr=lr / 2,
                 seed=seed)
    r3 = fit_regression(r2.params_raw, cfg, reg_data, steps=refit_steps,
                        lr=lr / 10, seed=seed)
    return r3.params_raw, {
        "sft_loss": r1.losses, "dpo_loss": r2.losses,
        "refit_loss": r3.losses,
    }


def regression_r2(params_raw, cfg, data: dict) -> np.ndarray:
    """Per-parser R^2 of the accuracy regression (paper: 40.0% / 46.5%)."""
    pred = np.asarray(enc_lib.predict_accuracies(
        params_raw, cfg, jnp.asarray(data["tokens"]),
        jnp.asarray(data["mask"])))
    y = np.asarray(data["targets"])
    ss_res = np.sum((pred - y) ** 2, axis=0)
    ss_tot = np.sum((y - y.mean(axis=0)) ** 2, axis=0) + 1e-12
    return 1.0 - ss_res / ss_tot
