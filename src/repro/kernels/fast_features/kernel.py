"""Pallas-TPU fused prepare-stage kernel: CLS-I fast features +
first-page token/mask assembly in one pass over the packed batch.

Grid: (ceil(n / 8),) — one program per block of ``BLOCK_D`` = 8
documents, so every VMEM block is a legal (8k, 128k) tile. The block's
padded token streams (8, L) and its per-doc scalars (token count,
first-page length, page counts; an (8, 4) int32 block) sit in VMEM, and
every reduction is a lane reduction with kept dims, so no value moves
between vector and scalar registers. All eight CLS-I features are
masked reductions over the stream. The distinct-token count is a
first-occurrence scan: position i is a duplicate iff some valid earlier
position j holds the same token. The scan walks j once per block —
token j's column is pulled out of a ``block_l``-lane slice (the
autotunable knob) and compared against all eight streams at once. The
first-page token/mask pair is the stream head shifted one lane right
under a BOS, exactly ``features.first_page_tokens``. The length feature's
log comes from ``f32math`` (the TPU's own log1p misses the oracle's 1e-6
parity).

Off-TPU the kernel runs in interpret mode (parity tests); dispatch for
real workloads goes through ``ops.routing_features``, which picks the
numpy oracle on CPU hosts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import f32math

N_FAST_FEATURES = 8
BLOCK_D = 8                              # documents per program (sublanes)
LANES = 128


def _ff_kernel(scal_ref, tok_ref, *refs, L: int, max_len: int,
               block_l: int, ws: int, scramble: int, mangled: int,
               latex_lo: int, ident_lo: int, bos: int):
    fast_ref = refs[0]
    t = tok_ref[...]                                      # (8, L) int32
    nt = scal_ref[:, 0:1]                                 # (8, 1) each
    first = scal_ref[:, 1:2]
    pages = scal_ref[:, 2:3]
    empty = scal_ref[:, 3:4]
    pos = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_D, L), 1)
    valid = pos < nt

    def count(mask):                                      # -> (8, 1) f32
        return jnp.sum(jnp.where(mask & valid, 1.0, 0.0), axis=1,
                       keepdims=True)

    n_ws = count(t == ws)
    n_scr = count(t == scramble)
    n_man = count(t == mangled)
    n_latex = count((t >= latex_lo) & (t < ident_lo))

    # distinct tokens: dup[d, i] = 1 iff a valid j < i has t[d, j] ==
    # t[d, i]. Column j is extracted from its block_l-lane slice by a
    # masked lane sum (no dynamic lane indexing), then compared against
    # the whole (8, L) block.
    lane = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_D, block_l), 1)
    dup = jnp.zeros((BLOCK_D, L), jnp.int32)
    for cb in range(L // block_l):
        tb = t[:, cb * block_l:(cb + 1) * block_l]        # static slice

        def scan_col(jj, dup, tb=tb, cb=cb):
            col = jnp.sum(jnp.where(lane == jj, tb, 0), axis=1,
                          keepdims=True)                  # (8, 1)
            j = cb * block_l + jj
            hit = (t == col) & (pos > j) & (j < nt)
            return jnp.maximum(dup, jnp.where(hit, 1, 0))

        dup = jax.lax.fori_loop(0, block_l, scan_col, dup)
    n_uniq = count(dup == 0)

    ntf = nt.astype(jnp.float32)
    denom = jnp.maximum(ntf, 1.0)
    pg = pages.astype(jnp.float32)
    feats = (f32math.log(ntf + 1.0) / 10.0, n_ws / denom, n_scr / denom,
             n_man / denom, n_latex / denom, n_uniq / denom,
             empty.astype(jnp.float32) / jnp.maximum(pg, 1.0), pg / 10.0)
    col8 = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_D, N_FAST_FEATURES),
                                    1)
    fast = jnp.zeros((BLOCK_D, N_FAST_FEATURES), jnp.float32)
    for k, f in enumerate(feats):
        fast = jnp.where(col8 == k, f, fast)
    # empty-extraction signature row: all zeros
    fast_ref[...] = jnp.where(nt > 0, fast, 0.0)

    if max_len:
        toks_ref, mask_ref = refs[1], refs[2]
        # shift the lane-aligned stream head one lane right under BOS
        # (pack guarantees L >= round_up(max_len, 128))
        w = -(-max_len // LANES) * LANES
        head = pltpu.roll(t[:, :w], 1, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_D, w), 1)
        m = jnp.minimum(first, max_len - 1)
        keep = col < 1 + m
        toks = jnp.where(col == 0, bos, jnp.where(keep, head, 0))
        toks_ref[...] = toks[:, :max_len]
        mask_ref[...] = jnp.where(keep, 1.0, 0.0)[:, :max_len]


@functools.partial(jax.jit, static_argnames=(
    "max_len", "block_l", "ws", "scramble", "mangled", "latex_lo",
    "ident_lo", "bos", "interpret"))
def fast_features_kernel(tok, n_tok, first_len, n_pages, n_empty, *,
                         max_len: int, block_l: int, ws: int,
                         scramble: int, mangled: int, latex_lo: int,
                         ident_lo: int, interpret: bool, bos: int = 1):
    """Packed batch -> (fast (n, 8) f32[, toks (n, max_len) i32,
    mask (n, max_len) f32]) on-device. ``max_len == 0`` skips the
    token/mask outputs (the ft router variant needs features only).

    ``tok`` is (n, L) with L a multiple of 128 (``pack_routing_batch``
    pads to a power of two >= 128); ``block_l`` must divide L. The batch
    is zero-padded to a multiple of 8 documents and sliced back."""
    n, L = tok.shape
    block_l = max(1, min(int(block_l), L))
    if L % block_l:
        raise ValueError(f"block_l={block_l} must divide packed width {L}")
    if L % LANES:
        raise ValueError(f"packed width {L} must be a multiple of {LANES}")
    if max_len and L < -(-max_len // LANES) * LANES:
        raise ValueError(f"packed width {L} < max_len={max_len} rounded "
                         f"up to {LANES} lanes")
    n_pad = -(-n // BLOCK_D) * BLOCK_D
    scal = jnp.stack([n_tok, first_len, n_pages, n_empty],
                     axis=1).astype(jnp.int32)
    tok = tok.astype(jnp.int32)
    if n_pad != n:
        scal = jnp.pad(scal, ((0, n_pad - n), (0, 0)))
        tok = jnp.pad(tok, ((0, n_pad - n), (0, 0)))
    kern = functools.partial(
        _ff_kernel, L=L, max_len=max_len, block_l=block_l, ws=ws,
        scramble=scramble, mangled=mangled, latex_lo=latex_lo,
        ident_lo=ident_lo, bos=bos)
    out_specs = [pl.BlockSpec((BLOCK_D, N_FAST_FEATURES), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((n_pad, N_FAST_FEATURES),
                                      jnp.float32)]
    if max_len:
        out_specs += [pl.BlockSpec((BLOCK_D, max_len), lambda i: (i, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct((n_pad, max_len), jnp.int32),
                      jax.ShapeDtypeStruct((n_pad, max_len), jnp.float32)]
    out = pl.pallas_call(
        kern,
        grid=(n_pad // BLOCK_D,),
        in_specs=[
            pl.BlockSpec((BLOCK_D, 4), lambda i: (i, 0)),      # scalars
            pl.BlockSpec((BLOCK_D, L), lambda i: (i, 0)),      # tokens
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(scal, tok)
    if max_len:
        return out[0][:n], out[1][:n], out[2][:n]
    return out[0][:n], None, None
