"""Pallas-TPU fused n-gram BLEU: per-position gram-match counts in VMEM.

The quality probe's scorer (metrics.score_batch) in one kernel. Clipped
counts without Counters: hyp occurrence i of an n-gram g is creditable
iff its occurrence rank among equal hyp grams (how many valid earlier
hyp starts j < i hold g) is below g's count in the reference (how many
valid ref starts hold g). Both counts are accumulated per hyp position
by one walk over the comparison position j: an n-gram match at (i, j)
is the (n-1)-gram match AND the tokens n-1 places later matching, so
the walk extends every order 1..max_n incrementally.

The token streams arrive pre-shifted (``max_n`` copies, copy t holding
the stream advanced by t, built by XLA in the wrapper), so "the token
n-1 places later" is a plain elementwise read — no in-kernel rolls.

Grid: (ceil(B / block_b),) — ``block_b`` documents per program on the
sublanes (the autotunable knob; a multiple of 8 to compile). Stream
blocks are (max_n, block_b, L) with L padded to a multiple of 128,
lengths an (block_b, 2) int32 block, scores an (block_b, 1) block.
Column j of each stream is extracted by a masked lane sum over a
128-lane slice, so there is no dynamic lane indexing and no value
crosses from vector to scalar registers. Padded rows score 0 and are
sliced off; padded positions lie past every length and are masked.
log and exp come from ``f32math``, float32-accurate on the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import f32math

SMOOTH = 1e-9
LANES = 128
SUBLANES = 8


def _ngram_bleu_kernel(len_ref, ref_ref, hyp_ref, out_ref, *,
                       max_len: int, max_n: int, block_b: int):
    lr = len_ref[:, 0:1]                                  # (bb, 1)
    lh = len_ref[:, 1:2]
    hs = [hyp_ref[t] for t in range(max_n)]               # (bb, L) each
    rs = [ref_ref[t] for t in range(max_n)]
    pos = jax.lax.broadcasted_iota(jnp.int32, (block_b, max_len), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_b, LANES), 1)
    zeros = jnp.zeros((block_b, max_len), jnp.int32)
    # occ[n]: valid earlier hyp starts holding the hyp gram at i;
    # rc[n]: valid ref starts holding it
    carry = (tuple([zeros] * max_n), tuple([zeros] * max_n))
    for cb in range(max_len // LANES):
        hb = [h[:, cb * LANES:(cb + 1) * LANES] for h in hs]
        rb = [r[:, cb * LANES:(cb + 1) * LANES] for r in rs]

        def walk(jj, carry, hb=hb, rb=rb, cb=cb):
            occ, rc = list(carry[0]), list(carry[1])
            j = cb * LANES + jj
            pick = lane == jj
            m_hh = m_hr = None
            for t in range(max_n):
                hc = jnp.sum(jnp.where(pick, hb[t], 0), axis=1,
                             keepdims=True)               # hyp[j + t]
                rcol = jnp.sum(jnp.where(pick, rb[t], 0), axis=1,
                               keepdims=True)             # ref[j + t]
                e_hh = hs[t] == hc
                e_hr = hs[t] == rcol
                m_hh = e_hh if m_hh is None else m_hh & e_hh
                m_hr = e_hr if m_hr is None else m_hr & e_hr
                n = t + 1
                occ[t] = occ[t] + jnp.where(
                    m_hh & (j < pos) & (j <= lh - n), 1, 0)
                rc[t] = rc[t] + jnp.where(m_hr & (j <= lr - n), 1, 0)
            return tuple(occ), tuple(rc)

        carry = jax.lax.fori_loop(0, LANES, walk, carry)
    occ, rc = carry
    log_p = jnp.zeros((block_b, 1), jnp.float32)
    for t in range(max_n):
        n = t + 1
        clipped = jnp.sum(
            jnp.where((pos <= lh - n) & (occ[t] < rc[t]), 1.0, 0.0),
            axis=1, keepdims=True)
        total = jnp.maximum(lh - n + 1, 1).astype(jnp.float32)
        log_p = log_p + f32math.log((clipped + SMOOTH) / total)
    log_p = log_p / max_n
    lrf = lr.astype(jnp.float32)
    lhf = lh.astype(jnp.float32)
    bp = jnp.minimum(1.0, f32math.exp(1.0 - lrf / jnp.maximum(lhf, 1.0)))
    out_ref[...] = jnp.where(lh > 0, bp * f32math.exp(log_p), 0.0)


def _shifted(x, max_n: int):
    """(B, L) -> (max_n, B, L): copy t is x advanced by t positions."""
    return jnp.stack([jnp.pad(x[:, t:], ((0, 0), (0, t)))
                      for t in range(max_n)])


@functools.partial(jax.jit, static_argnames=("max_len", "max_n",
                                             "interpret", "block_b"))
def ngram_bleu_kernel(ref, hyp, lr, lh, *, max_len: int, interpret: bool,
                      max_n: int = 4, block_b: int = SUBLANES):
    """ref, hyp (B, max_len) int32 padded; lr, lh (B,) int32 lengths.

    Returns (B,) f32 per-document BLEU. ``block_b`` is the autotunable
    docs-per-program block (clamped to [1, B rounded up to 8]; compiled
    mode needs a multiple of 8)."""
    b = ref.shape[0]
    block_b = max(1, min(int(block_b), -(-b // SUBLANES) * SUBLANES))
    if not interpret and block_b % SUBLANES:
        raise ValueError(f"block_b={block_b} is not a multiple of "
                         f"{SUBLANES}: a compiled (block_b, L) block "
                         f"would not tile the TPU's (8, 128) registers")
    b_pad = -(-b // block_b) * block_b
    l_pad = -(-max_len // LANES) * LANES
    pad = ((0, b_pad - b), (0, l_pad - max_len))
    ref = _shifted(jnp.pad(ref.astype(jnp.int32), pad), max_n)
    hyp = _shifted(jnp.pad(hyp.astype(jnp.int32), pad), max_n)
    lens = jnp.pad(jnp.stack([lr, lh], axis=1).astype(jnp.int32),
                   ((0, b_pad - b), (0, 0)))
    kern = functools.partial(_ngram_bleu_kernel, max_len=l_pad,
                             max_n=max_n, block_b=block_b)
    stream = pl.BlockSpec((max_n, block_b, l_pad), lambda i: (0, i, 0))
    out = pl.pallas_call(
        kern,
        grid=(b_pad // block_b,),
        in_specs=[pl.BlockSpec((block_b, 2), lambda i: (i, 0)),  # lengths
                  stream, stream],                               # ref, hyp
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, 1), jnp.float32),
        interpret=interpret,
    )(lens, ref, hyp)
    return out[:b, 0]
