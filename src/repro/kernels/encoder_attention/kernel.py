"""Pallas-TPU bidirectional encoder attention: one row of keys in VMEM.

The attention core of the router's encoder (``models.encoder``) for
sequences short enough that a query block's scores against every key
fit in VMEM (S <= MAX_SEQ: one 512 x 512 f32 tile is 1 MiB). A program
computes, per head, the scores ``q . k^T`` (bf16 on the MXU, f32
accumulation), the scale and the per-document key bias in f32, a
single-pass softmax (max, ``exp``, row sum: every key is visible, so
there is no online rescaling) and ``exp(s - m) . v``, normalised by the
row sum in f32 after the product. Scores and probabilities never leave
VMEM; HBM sees q, k, v and the output once each.

Layout: q, k, v and the output are (B, S, H * Dh), the row-major view of
the projections' (B, S, H, Dh), so no transpose sits around the call;
head ``h`` is the lane slice ``[h * Dh, (h + 1) * Dh)``. The bias is
(B, 1, S) f32: 0 on real keys, ``models.attention.NEG_INF`` on padding.

Grid: (B,): one program per document, all its heads. A block spans the
whole (S, H * Dh) row, so it tiles for any head width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: longest sequence whose (S, S) f32 score tile the kernel holds in VMEM
MAX_SEQ = 512


def _encoder_attention_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, *,
                              heads: int, d_head: int, scale: float):
    bias = bias_ref[0]                                    # (1, S) f32
    for h in range(heads):
        cols = slice(h * d_head, (h + 1) * d_head)
        s = jax.lax.dot_general(q_ref[0, :, cols], k_ref[0, :, cols],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale + bias                              # (S, S) f32
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        l = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.dot(e.astype(v_ref.dtype), v_ref[0, :, cols],
                    preferred_element_type=jnp.float32)
        o_ref[0, :, cols] = (o / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encoder_attention_kernel(q, k, v, bias, *, interpret: bool):
    """q, k, v (B, S, H, Dh); bias (B, S) f32 over keys. Returns the
    heads' outputs side by side, (B, S, H * Dh) in q's dtype."""
    b, s, h, d = q.shape
    if s > MAX_SEQ:
        raise ValueError(f"sequence length {s} > {MAX_SEQ}: the kernel "
                         f"holds a whole ({s}, {s}) f32 score row in VMEM")
    row = pl.BlockSpec((1, s, h * d), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_encoder_attention_kernel, heads=h, d_head=d,
                          scale=d ** -0.5),
        grid=(b,),
        in_specs=[row, row, row,
                  pl.BlockSpec((1, 1, s), lambda i: (i, 0, 0))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q.reshape(b, s, h * d), k.reshape(b, s, h * d),
      v.reshape(b, s, h * d), bias.reshape(b, 1, s))
