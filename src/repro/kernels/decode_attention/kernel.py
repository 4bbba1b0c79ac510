"""Pallas-TPU decode attention: one query row per slot, read only for
the slots that are live.

A greedy decode step of ``models.nougat`` attends one query row per page
slot to keys and values cached one row of H * Dh per position: the
self-attention cache's first blocks, each (slots, 128, H * Dh), or a
layer's cross-attention K/V, (slots, 592, H * Dh): 588 encoder tokens
and the rows that make them whole (16, 128) tiles. Only the slots whose
page is still decoding (live) need the result.

Grid: the slots in the order scalar prefetch gives (``order``: the live
slots first), ``group`` of them a step. Each of a step's ``group``
places is a K/V input of its own, whose block is that place's slot,
straight from the cache as it lies. Once the live slots run out, a
place keeps the block it last held (``blocks_of``), so the pipeline
issues no DMA for it; its body is skipped, and its slot's output row
stays zero. A dead slot costs no HBM traffic and no compute. Grouping
slots cuts the grid's per-step cost (96 slots at 8 a step: 12 steps).

Per live slot, all heads at once: the query row is spread over each
head's own Dh columns of an (H, H * Dh) block-diagonal matrix, so the
scores are one (H, positions) product against the cached rows (bf16 on
the MXU, f32 accumulation). Positions after ``last`` get no weight: the
self-attention cache past the step's own position, the cross-attention
rows past the encoder's tokens. A single-pass f32 softmax (every
position is in VMEM) normalises them; the probabilities, cast to the values' dtype, weigh the
values in a second product, of which each head keeps its own Dh columns.
This is ``models.nougat._attend_rows``' arithmetic.

q and the output are (slots, H * Dh), each whole in VMEM across the
grid; a slot's row is read and written within its aligned group of
``SUBLANES`` rows (Mosaic loads no single row at a dynamic offset).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SUBLANES = 8
#: VMEM for the K/V blocks of a step's slots, two buffers each: 8 slots
#: of 7 self-attention blocks (128 x 1024 bf16, keys and values) take 56
#: MiB, of a v5e's 128
KV_VMEM = 64 * 1024 * 1024
MAX_GROUP = 8


def slots_per_step(slots: int, kv_bytes_per_slot: int) -> int:
    """The most slots a grid step takes (a power of two up to
    ``MAX_GROUP`` that divides ``slots``) whose double-buffered K/V fit
    ``KV_VMEM``."""
    g = MAX_GROUP
    while g > 1 and (slots % g or 2 * g * kv_bytes_per_slot > KV_VMEM):
        g //= 2
    return g


def blocks_of(order, count, group: int):
    """(S,) int32: the slot whose K/V block place ``p % group`` holds at
    grid step ``p // group``: ``order[p]`` while p is live (p < count),
    then that place's last live slot (its first slot, ``order[p %
    group]``, if it never had one)."""
    p = jnp.arange(order.shape[0], dtype=jnp.int32)
    j = p % group
    last = j + group * ((count - 1 - j) // group)
    return order[jnp.minimum(p, jnp.where(count > j, last, j))]


def _decode_attention_kernel(order_ref, src_ref, count_ref, last_ref, q_ref,
                             *refs, group: int, blocks: int, heads: int):
    kv, o_ref = refs[:-1], refs[-1]
    step = pl.program_id(0)
    width = q_ref.shape[1]
    own = (lax.broadcasted_iota(jnp.int32, (heads, width), 1)
           // (width // heads)
           == lax.broadcasted_iota(jnp.int32, (heads, width), 0))

    @pl.when(step == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    for j in range(group):
        ks = kv[j * blocks:(j + 1) * blocks]
        vs = kv[(group + j) * blocks:(group + j + 1) * blocks]

        @pl.when(step * group + j < count_ref[0])
        def _():
            slot = order_ref[step * group + j]
            at = pl.ds(pl.multiple_of(slot // SUBLANES * SUBLANES, SUBLANES),
                       SUBLANES)
            mine = (lax.broadcasted_iota(jnp.int32, (SUBLANES, width), 0)
                    == slot % SUBLANES)
            q = jnp.sum(jnp.where(mine, q_ref[at, :].astype(jnp.float32),
                                  0.0), axis=0, keepdims=True)
            qb = jnp.where(own, jnp.broadcast_to(q, own.shape),
                           0.0).astype(q_ref.dtype)          # (H, width)
            s = []
            for b, k in enumerate(ks):
                sb = lax.dot_general(qb, k[...], (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                pos = b * k.shape[0] + lax.broadcasted_iota(jnp.int32,
                                                            sb.shape, 1)
                s.append(jnp.where(pos <= last_ref[0], sb, NEG_INF))
            m = functools.reduce(jnp.maximum, [jnp.max(sb, axis=1,
                                                       keepdims=True)
                                               for sb in s])
            e = [jnp.exp(sb - m) for sb in s]
            total = sum(jnp.sum(eb, axis=1, keepdims=True) for eb in e)
            o = sum(jnp.dot((eb / total).astype(v.dtype), v[...],
                            preferred_element_type=jnp.float32)
                    for eb, v in zip(e, vs))                 # (H, width)
            row = jnp.sum(jnp.where(own, o, 0.0), axis=0, keepdims=True)
            o_ref[at, :] = jnp.where(
                mine, row.astype(o_ref.dtype).astype(jnp.float32),
                o_ref[at, :].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def decode_attention_kernel(q, ks, vs, order, count, last, *, heads: int,
                            interpret: bool):
    """q (S, H * Dh), every head's query in its own Dh columns; ``ks``,
    ``vs``: sequences of equal length of (S, T, H * Dh) keys and values,
    block b holding positions [b * T, (b + 1) * T); ``order`` (S,) int32,
    the live slots first; ``count`` their number; positions after ``last``
    get no weight. Returns (S, H * Dh) in q's dtype, zero in every slot
    past the first ``count`` of ``order``."""
    s, width = q.shape
    if s % SUBLANES:
        raise ValueError(f"{s} slots are not whole groups of {SUBLANES} "
                         f"rows")
    blocks, rows = len(ks), ks[0].shape[1]
    per_slot = 2 * blocks * rows * width * ks[0].dtype.itemsize
    group = slots_per_step(s, per_slot)
    scalars = tuple(jnp.reshape(a, (-1,)).astype(jnp.int32) for a in (
        order, blocks_of(order, count, group), count, last))
    whole = pl.BlockSpec((s, width), lambda i, *_: (0, 0))

    def place(j):
        return pl.BlockSpec((None, rows, width),
                            lambda i, order, src, *_: (src[i * group + j],
                                                       0, 0))
    places = [place(j) for j in range(group) for _ in range(blocks)]
    return pl.pallas_call(
        functools.partial(_decode_attention_kernel, group=group,
                          blocks=blocks, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(s // group,),
            in_specs=[whole] + places + places,
            out_specs=whole),
        out_shape=jax.ShapeDtypeStruct((s, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * group * per_slot + 16 * 1024 * 1024),
        interpret=interpret,
    )(*scalars, q, *[a for _ in range(group) for a in ks],
      *[a for _ in range(group) for a in vs])
