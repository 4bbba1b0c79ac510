"""Pallas-TPU EmbeddingBag: fused gather + weighted segment reduce.

out[b] = combine_{l < L} w[b, l] * table[ids[b, l]]

The table stays in ANY/HBM memory space; rows are pulled with dynamic
loads inside the kernel (on real TPU this lowers to per-row DMA — the
FBGEMM-TBE pattern); ids/weights tiles and the output tile live in VMEM.
Grid: (n_batch_blocks,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bag_kernel(ids_ref, w_ref, table_ref, out_ref, *, bag: int,
                block_b: int, mean: bool):
    acc = jnp.zeros(out_ref.shape, jnp.float32)

    ids = ids_ref[...]
    ws = w_ref[...]

    def body(l, acc):
        def row(b, acc):
            rid = jax.lax.dynamic_index_in_dim(ids, b, 0,
                                               keepdims=False)[l]
            vec = table_ref[pl.dslice(rid, 1), :].astype(jnp.float32)
            wv = jax.lax.dynamic_index_in_dim(ws, b, 0, keepdims=False)[l]
            return jax.lax.dynamic_update_slice_in_dim(
                acc, jax.lax.dynamic_slice_in_dim(acc, b, 1) + vec * wv,
                b, axis=0)
        return jax.lax.fori_loop(0, block_b, row, acc)

    acc = jax.lax.fori_loop(0, bag, body, acc)
    if mean:
        denom = jnp.maximum(jnp.sum(w_ref[...], axis=1), 1e-9)[:, None]
        acc = acc / denom
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("combiner", "block_b",
                                             "interpret"))
def embedding_bag_kernel(table, ids, weights, *, combiner: str = "sum",
                         block_b: int = 64, interpret: bool):
    """table (R, D); ids (B, L) int32; weights (B, L) f32 -> (B, D)."""
    b, bag = ids.shape
    r, d = table.shape
    block_b = min(block_b, b)
    pad = (-b) % block_b
    if pad:
        ids = jnp.pad(ids, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    grid = ((b + pad) // block_b,)
    kern = functools.partial(_bag_kernel, bag=bag, block_b=block_b,
                             mean=(combiner == "mean"))
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, bag), lambda i: (i, 0)),
            pl.BlockSpec((block_b, bag), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),          # full table
        ],
        out_specs=pl.BlockSpec((block_b, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b + pad, d), table.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), weights.astype(jnp.float32), table)
    return out[:b]
