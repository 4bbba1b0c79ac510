"""Pallas-TPU flash attention (forward): blocked online-softmax.

Grid: (batch*kv_heads*q_groups, n_q_blocks, n_kv_blocks) — the kv-block
dim is innermost/sequential so the (m, l, o) accumulators live in VMEM
scratch across kv steps. Q/K/V tiles are BlockSpec'd into VMEM with
MXU-aligned (block_q, d_head) / (block_k, d_head) shapes; block sizes are
multiples of 128 where the head dim allows.

Causal + sliding-window masking is applied inside the tile; fully-masked
tiles are skipped at trace time via the grid index-map pruning trick
(we still visit them but exit early with @pl.when — on TPU the bandwidth
win comes from the early exit before the MXU issue).

This kernel is the TPU target of ``models.attention.attention_xla_flash``
(the XLA fallback used by CPU dry-runs); both share the ref oracle.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int | None,
               block_q: int, block_k: int, seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * block_q
    k_lo = ki * block_k
    # static-shape visibility: skip tiles fully masked by causality/window
    run = True
    if causal or window is not None:
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        visible = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            visible &= qpos >= kpos
        if window is not None:
            visible &= (qpos - kpos) < window
        any_visible = jnp.any(visible)
    else:
        visible = None
        any_visible = jnp.bool_(True)

    @pl.when(any_visible)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (block_q, d)
        k = k_ref[0].astype(jnp.float32)            # (block_k, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos1 = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
        mask = kpos1 < seq_kv                        # kv padding
        if visible is not None:
            mask &= visible
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)[:, None]
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_kernel(q, k, v, *, causal=True, window=None,
                           block_q=128, block_k=128, interpret: bool):
    """q (B, Sq, H, D); k, v (B, Skv, Hk, D); H % Hk == 0.

    Grouped heads are folded into the batch dim: each (b, kv_head, group)
    triple is an independent attention problem over its kv stream.
    """
    b, sq, h, d = q.shape
    _, skv, hk, _ = k.shape
    g = h // hk
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pq, pk = (-sq) % block_q, (-skv) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sq_p, skv_p = sq + pq, skv + pk
    # (B, S, Hk, G, D) -> (B*Hk*G, S, D)
    qf = q.reshape(b, sq_p, hk, g, d).transpose(0, 2, 3, 1, 4) \
          .reshape(b * hk * g, sq_p, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hk, skv_p, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hk, skv_p, d)

    grid = (b * hk * g, sq_p // block_q, skv_p // block_k)
    kern = functools.partial(_fa_kernel, scale=scale, causal=causal,
                             window=window, block_q=block_q,
                             block_k=block_k, seq_kv=skv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g_=g: (bh // g_, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki, g_=g: (bh // g_, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hk * g, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, hk, g, sq_p, d).transpose(0, 3, 1, 2, 4) \
             .reshape(b, sq_p, h, d)
    return out[:, :sq]
