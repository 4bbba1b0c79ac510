"""BERT-style encoder with a per-parser accuracy head: seeded weights
and the plain float32 forward pass the CLS-III route step is checked
against.

Architecture (SciBERT-base, arXiv:1903.10676, as the router uses it):
token plus learned position embeddings, LayerNorm; per layer,
multi-head self-attention over the unmasked positions, residual,
LayerNorm, a GELU feed-forward, residual, LayerNorm (post-norm); a tanh
pooler on position 0; a sigmoid head with one output per parser. The
router's GELU is the tanh approximation, where BERT states the erf
form; the reference follows the router and the departure is listed in
PERF.md.

Weights are made on the device in one jitted call from the seed, in
the type the configuration serves them in, in the layer-stacked layout
the route step takes: ``layers/wq`` is (L, d, heads, d_head) and so on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def shapes(enc: dict) -> dict:
    """Leaf name -> (shape, init std, or "zeros"/"ones")."""
    L, d, h, f = (enc["n_layers"], enc["d_model"], enc["n_heads"],
                  enc["d_ff"])
    dh = d // h
    layer = {
        "wq": ((L, d, h, dh), d ** -0.5), "wk": ((L, d, h, dh), d ** -0.5),
        "wv": ((L, d, h, dh), d ** -0.5), "wo": ((L, h, dh, d), d ** -0.5),
        "ln1_s": ((L, d), "ones"), "ln1_b": ((L, d), "zeros"),
        "w_in": ((L, d, f), d ** -0.5), "b_in": ((L, f), "zeros"),
        "w_out": ((L, f, d), f ** -0.5), "b_out": ((L, d), "zeros"),
        "ln2_s": ((L, d), "ones"), "ln2_b": ((L, d), "zeros"),
    }
    return {
        "tok_embed": ((enc["vocab_size"], d), 0.02),
        "pos_embed": ((enc["max_len"], d), 0.02),
        "ln_embed_s": ((d,), "ones"), "ln_embed_b": ((d,), "zeros"),
        "layers": layer,
        "pool_w": ((d, d), d ** -0.5), "pool_b": ((d,), "zeros"),
        "head_w": ((d, enc["n_outputs"]), d ** -0.5),
        "head_b": ((enc["n_outputs"],), "zeros"),
        "pref_w": ((d, 1), d ** -0.5), "pref_b": ((1,), "zeros"),
    }


def init(enc: dict, seed: int):
    """Seeded weights, on the device, in ``enc["param_dtype"]``."""
    spec = shapes(enc)
    dtype = jnp.dtype(enc["param_dtype"])
    leaves, tree = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, std) in zip(keys, leaves):
            if std == "zeros":
                out.append(jnp.zeros(shape, dtype))
            elif std == "ones":
                out.append(jnp.ones(shape, dtype))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(jax.random.key(seed % 2 ** 32))


def center_head(params, enc: dict, tokens: np.ndarray, mask: np.ndarray,
                cheap: int, expensive: int, share: float):
    """``params`` with the expensive parser's head bias moved so that a
    ``share`` of the given documents predicts a higher accuracy for the
    expensive parser than for the cheap one: a positive improvement, as
    a trained router gives the documents worth re-parsing. Seeded
    weights alone put one side of zero under nearly every document on
    some seeds."""
    p = predict(params, enc, tokens, mask).astype(np.float64)
    logit = np.log(p) - np.log1p(-p)
    shift = -np.quantile(logit[:, expensive] - logit[:, cheap], 1.0 - share)
    head_b = params["head_b"].at[expensive].add(shift)
    return dict(params, head_b=head_b)


def _quantize(x):
    """float8 e4m3 with one scale per tensor (the control's precision)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ln(x, s, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * s + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "control"))
def _forward(params, tokens, mask, *, n_heads: int, eps: float,
             control: bool):
    q8 = _quantize if control else (lambda a: a)

    def mm(a, w):
        return jnp.matmul(q8(a), q8(w), precision="highest")

    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    b, s = tokens.shape
    x = p["tok_embed"][tokens] + p["pos_embed"][:s][None]
    x = _ln(x, p["ln_embed_s"], p["ln_embed_b"], eps)
    bias = jnp.where(mask > 0, 0.0, NEG_INF)[:, None, None, :]
    d = x.shape[-1]
    dh = d // n_heads

    def layer(x, lp):
        q, k, v = (mm(x, lp[w].reshape(d, d)).reshape(b, s, n_heads, dh)
                   for w in ("wq", "wk", "wv"))
        att = jnp.einsum("bqhd,bkhd->bhqk", q8(q), q8(k),
                         precision="highest") / np.sqrt(dh) + bias
        att = jax.nn.softmax(att, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", q8(att), q8(v),
                       precision="highest").reshape(b, s, d)
        x = _ln(x + mm(o, lp["wo"].reshape(d, d)), lp["ln1_s"], lp["ln1_b"],
                eps)
        h = _gelu(mm(x, lp["w_in"]) + lp["b_in"])
        return _ln(x + mm(h, lp["w_out"]) + lp["b_out"], lp["ln2_s"],
                   lp["ln2_b"], eps), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    pooled = jnp.tanh(mm(x[:, 0], p["pool_w"]) + p["pool_b"])
    return jax.nn.sigmoid(mm(pooled, p["head_w"]) + p["head_b"])


def predict(params, enc: dict, tokens: np.ndarray, mask: np.ndarray,
            precision: str = "exact", block: int = 64) -> np.ndarray:
    """(n, max_len) tokens and mask -> (n, n_outputs) predicted accuracy,
    float32 at the highest matmul precision (``control``: weights and
    matmul operands in float8), ``block`` rows at a time."""
    if precision not in ("exact", "control"):
        raise ValueError(f"unknown precision {precision!r}")
    out = []
    for i in range(0, len(tokens), block):
        out.append(np.asarray(_forward(
            params, jnp.asarray(tokens[i:i + block]),
            jnp.asarray(mask[i:i + block]), n_heads=enc["n_heads"],
            eps=float(enc["norm_eps"]), control=precision == "control")))
    return np.concatenate(out)
