"""BERT-style bidirectional encoder — the AdaParse CLS-III router model
(SciBERT-class, ~110M at full config). Supports:

- per-parser accuracy regression head (m outputs in [0,1]) — stage-1 SFT
  target of Appendix A;
- scalar preference head — the g_phi scorer used by DPO (stage 2);
- multi-class parser-selection readout (argmax over predicted accuracies).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import KeyGen, normal_init, param
from repro.configs.base import EncoderConfig
from repro.distributed.meshrules import shard_hint
from repro.models import attention as attn_lib
from repro.models.layers import embed_lookup, gelu, layer_norm


def init_encoder(cfg: EncoderConfig, seed: int = 0, abstract: bool = False):
    kg = None if abstract else KeyGen(seed)
    dtype = jnp.dtype(cfg.param_dtype)
    d, h, f, L = cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.n_layers
    dh = d // h

    def mk(shape, axes, std, layers=True):
        lead, laxes = ((L,), ("layers",)) if layers else ((), ())
        return param(None if abstract else kg(), lead + shape, laxes + axes,
                     normal_init(std), dtype, abstract)

    def mkz(shape, axes, layers=True):
        lead, laxes = ((L,), ("layers",)) if layers else ((), ())
        return param(None, lead + shape, laxes + axes,
                     lambda k, s, t: jnp.zeros(s, t), dtype, abstract)

    def mko(shape, axes, layers=True):
        lead, laxes = ((L,), ("layers",)) if layers else ((), ())
        return param(None, lead + shape, laxes + axes,
                     lambda k, s, t: jnp.ones(s, t), dtype, abstract)

    layer = {
        "wq": mk((d, h, dh), ("d_model", "heads", "d_head"), d ** -0.5),
        "wk": mk((d, h, dh), ("d_model", "heads", "d_head"), d ** -0.5),
        "wv": mk((d, h, dh), ("d_model", "heads", "d_head"), d ** -0.5),
        "wo": mk((h, dh, d), ("heads", "d_head", "d_model"), d ** -0.5),
        "ln1_s": mko((d,), ("d_model",)),
        "ln1_b": mkz((d,), ("d_model",)),
        "w_in": mk((d, f), ("d_model", "d_ff"), d ** -0.5),
        "b_in": mkz((f,), ("d_ff",)),
        "w_out": mk((f, d), ("d_ff", "d_model"), f ** -0.5),
        "b_out": mkz((d,), ("d_model",)),
        "ln2_s": mko((d,), ("d_model",)),
        "ln2_b": mkz((d,), ("d_model",)),
    }
    return {
        "tok_embed": param(None if abstract else kg(), (cfg.vocab_size, d),
                           ("vocab", "d_model"), normal_init(0.02), dtype,
                           abstract),
        "pos_embed": param(None if abstract else kg(), (cfg.max_len, d),
                           ("pos", "d_model"), normal_init(0.02), dtype,
                           abstract),
        "ln_embed_s": mko((d,), ("d_model",), layers=False),
        "ln_embed_b": mkz((d,), ("d_model",), layers=False),
        "layers": layer,
        "pool_w": mk((d, d), ("d_model", None), d ** -0.5, layers=False),
        "pool_b": mkz((d,), (None,), layers=False),
        "head_w": mk((d, cfg.n_outputs), ("d_model", None), d ** -0.5,
                     layers=False),
        "head_b": mkz((cfg.n_outputs,), (None,), layers=False),
        "pref_w": mk((d, 1), ("d_model", None), d ** -0.5, layers=False),
        "pref_b": mkz((1,), (None,), layers=False),
    }


def dot_attention(q, k, v, bias, wo):
    """The attention block as einsums: q, k, v (B, S, H, Dh), bias (B, S)
    f32 over keys, wo (H, Dh, D) -> (B, S, D). Differentiable; the route
    step swaps in ``kernels.encoder_attention`` (same math, scores kept
    in VMEM)."""
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    # heads (12) don't divide model=16 — shard the q-seq dim of the
    # score tensor instead (else (B,H,S,S) fp32 replicates over model)
    s = shard_hint(s, "batch", None, "seq", None)
    s = s + bias[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return jnp.einsum("bqhd,hdm->bqm", o, wo)


def _enc_layer(cfg: EncoderConfig, attention=dot_attention):
    cdt = jnp.dtype(cfg.compute_dtype)

    def layer(carry, lp):
        x, bias = carry
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(cdt))
        k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"].astype(cdt))
        v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"].astype(cdt))
        o = attention(q, k, v, bias, lp["wo"].astype(cdt))
        x = layer_norm(x + o, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
        h = gelu(jnp.einsum("bsd,df->bsf", x, lp["w_in"].astype(cdt))
                 + lp["b_in"].astype(cdt))
        # d_ff (not seq) takes the model axis here — the hidden tensor is
        # the layer's biggest (B, S, 4d); seq-sharding it would block TP
        h = shard_hint(h, "batch", None, "d_ff")
        h = jnp.einsum("bsf,fd->bsd", h, lp["w_out"].astype(cdt)) \
            + lp["b_out"].astype(cdt)
        x = layer_norm(x + h, lp["ln2_s"], lp["ln2_b"], cfg.norm_eps)
        x = shard_hint(x, "batch", "seq", "d_model")
        return (x, bias), None

    return layer


def encode(params_raw, cfg: EncoderConfig, tokens: jax.Array,
           mask: jax.Array | None = None,
           attention=dot_attention) -> jax.Array:
    """tokens (B, S) -> pooled CLS representation (B, D). ``attention``
    is each layer's attention block, with ``dot_attention``'s signature."""
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s = tokens.shape
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    x = embed_lookup(params_raw["tok_embed"].astype(cdt), tokens)
    x = x + params_raw["pos_embed"][:s].astype(cdt)[None]
    x = layer_norm(x, params_raw["ln_embed_s"], params_raw["ln_embed_b"],
                   cfg.norm_eps)
    x = shard_hint(x, "batch", "seq", "d_model")
    bias = jnp.where(mask > 0, 0.0, attn_lib.NEG_INF).astype(jnp.float32)
    layer = _enc_layer(cfg, attention)
    if cfg.remat:
        layer = jax.checkpoint(layer,
                               policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.scan_layers:
        (x, _), _ = jax.lax.scan(layer, (x, bias), params_raw["layers"])
    else:
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params_raw["layers"])
            (x, bias), _ = layer((x, bias), lp)
    pooled = jnp.tanh(jnp.einsum("bd,de->be", x[:, 0],
                                 params_raw["pool_w"].astype(cdt))
                      + params_raw["pool_b"].astype(cdt))
    return pooled


def predict_accuracies(params_raw, cfg: EncoderConfig, tokens, mask=None,
                       attention=dot_attention):
    """(B, S) tokens -> (B, m) predicted per-parser accuracy in [0, 1]."""
    pooled = encode(params_raw, cfg, tokens, mask, attention)
    out = jnp.einsum("bd,dm->bm", pooled, params_raw["head_w"].astype(pooled.dtype))
    out = out + params_raw["head_b"].astype(pooled.dtype)
    return jax.nn.sigmoid(out.astype(jnp.float32))


def preference_score(params_raw, cfg: EncoderConfig, tokens, mask=None):
    """g_phi(x): positive scalar preference density (B,) for DPO."""
    pooled = encode(params_raw, cfg, tokens, mask)
    z = jnp.einsum("bd,do->bo", pooled, params_raw["pref_w"].astype(pooled.dtype))
    z = z + params_raw["pref_b"].astype(pooled.dtype)
    return jax.nn.softplus(z.astype(jnp.float32))[:, 0] + 1e-6


def regression_loss(params_raw, cfg: EncoderConfig, batch):
    """L_REG = E ||pi(x) - y||^2 with a validity mask over parsers."""
    pred = predict_accuracies(params_raw, cfg, batch["tokens"],
                              batch.get("mask"))
    y = batch["targets"].astype(jnp.float32)
    w = batch.get("target_mask")
    err = jnp.square(pred - y)
    if w is not None:
        w = w.astype(jnp.float32)
        return jnp.sum(err * w) / jnp.maximum(jnp.sum(w), 1.0)
    return jnp.mean(err)
