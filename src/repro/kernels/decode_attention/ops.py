"""Decode attention over page slots: the Pallas kernel where it applies,
``models.nougat._attend_rows`` (the einsum form, this op's oracle)
elsewhere.

``uses_kernel`` is the dispatch rule, decided from what the caller can
observe: the backend (a TPU, or ``force_kernel``, which runs the kernel
in interpret mode off-TPU, as ``encoder_attention`` does) and the row
width: rows of whole 128-lane tiles. Narrower rows (the tiny preset's
d 32) keep the einsum form.

``live_slots`` turns a step's liveness into the kernel's scalar
prefetch, once a step for all its calls: the live slots first, in slot
order, and their number.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import encoder_attention
from repro.kernels.decode_attention.kernel import decode_attention_kernel

LANES = 128


def uses_kernel(width: int, force_kernel: bool = False) -> bool:
    return width % LANES == 0 and encoder_attention.uses_kernel(force_kernel)


def live_slots(live):
    """(order, count) of a (S,) bool: slot indices, the live ones first in
    slot order, and how many are live."""
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    return order.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)


def decode_attention(q, ks, vs, live, last):
    """q (S, H, Dh) against keys and values given as blocks of positions,
    each (S, T, H * Dh), positions after ``last`` masked, for the live
    slots only (``live_slots``' (order, count)). Returns (S, H * Dh),
    zero rows for the slots that are not live."""
    s, h, d = q.shape
    order, count = live
    return decode_attention_kernel(q.reshape(s, h * d), list(ks), list(vs),
                                   order, count, last, heads=h,
                                   interpret=jax.default_backend() != "tpu")
