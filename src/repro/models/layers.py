"""Shared neural layers: norms, rotary embeddings, dense/einsum layers,
activations, embeddings. All functions are pure; parameters are Param
trees (see repro.common)."""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.common import (Param, fan_in_init, normal_init, ones_init, param,
                          zeros_init)
from repro.distributed.meshrules import shard_hint

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-12) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def init_rms_norm(d: int, dtype, abstract=False, layers: int | None = None) -> Param:
    shape = (d,) if layers is None else (layers, d)
    axes = ("d_model",) if layers is None else ("layers", "d_model")
    return param(None, shape, axes, zeros_init, dtype, abstract)


def init_layer_norm(d: int, dtype, abstract=False, layers: int | None = None):
    shape = (d,) if layers is None else (layers, d)
    axes = ("d_model",) if layers is None else ("layers", "d_model")
    return {
        "scale": param(None, shape, axes, ones_init, dtype, abstract),
        "bias": param(None, shape, axes, zeros_init, dtype, abstract),
    }


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    half = d_head // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, d_head); positions: broadcastable to (..., seq)."""
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)                       # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    angles = angles[..., :, None, :]                              # (..., S, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / einsum layers
# ---------------------------------------------------------------------------


def init_dense(key, d_in: int, d_out: int, axes: Sequence[str | None],
               dtype, abstract=False, bias: bool = False,
               layers: int | None = None, stddev: float | None = None):
    shape = (d_in, d_out)
    if layers is not None:
        shape = (layers,) + shape
        axes = ("layers",) + tuple(axes)
    init = normal_init(stddev) if stddev is not None else fan_in_init(
        1 if layers is not None else 0)
    p = {"w": param(key, shape, axes, init, dtype, abstract)}
    if bias:
        bshape = (d_out,) if layers is None else (layers, d_out)
        baxes = (axes[-1],) if layers is None else ("layers", axes[-1])
        p["b"] = param(None, bshape, baxes, zeros_init, dtype, abstract)
    return p


def dense(x: jax.Array, p, out_hint: tuple[str | None, ...] | None = None):
    w = p["w"].value if isinstance(p["w"], Param) else p["w"]
    y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    if "b" in p:
        b = p["b"].value if isinstance(p["b"], Param) else p["b"]
        y = y + b.astype(y.dtype)
    if out_hint is not None:
        y = shard_hint(y, *out_hint)
    return y


def mlp_stack(key_gen, dims: Sequence[int], dtype, abstract=False,
              in_axis: str | None = None, hidden_axis: str | None = "d_ff",
              bias: bool = True):
    """A plain MLP as a list of dense layers; hidden dims sharded on
    ``hidden_axis``, final output replicated."""
    layers = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        last = i == len(dims) - 2
        ax_in = in_axis if i == 0 else hidden_axis
        ax_out = None if last else hidden_axis
        layers.append(init_dense(None if abstract else key_gen(), a, b,
                                 (ax_in, ax_out), dtype, abstract, bias=bias))
    return layers


def mlp_apply(x: jax.Array, layers, act=jax.nn.relu, final_act=None):
    for i, p in enumerate(layers):
        x = dense(x, p)
        if i < len(layers) - 1:
            x = act(x)
        elif final_act is not None:
            x = final_act(x)
    return x


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def gelu(x: jax.Array, approximate: bool = True) -> jax.Array:
    """GELU: the tanh approximation (the router's), or with
    ``approximate=False`` the erf form (Swin's and mBART's)."""
    return jax.nn.gelu(x, approximate=approximate)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, dtype, abstract=False,
                   axes=("vocab", "d_model")) -> Param:
    return param(key, (vocab, d), axes, normal_init(0.02), dtype, abstract)


def embed_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)


def softcap(logits: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """Mean token-level CE in fp32. logits (..., V), labels (...)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
