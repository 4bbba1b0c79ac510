"""The route step's encoder attention block: the Pallas kernel, then the
output projection over the kernel's merged-head rows (so the projection
reads them with no relayout). Forward-only; same signature as
``models.encoder.dot_attention``, the einsum block that the
differentiable losses keep and that is this op's oracle.

``uses_kernel`` is the dispatch rule ``budget_route`` follows: the
kernel on TPU backends, or under ``force_kernel`` (interpret mode
off-TPU); the caller takes the einsum block otherwise. It asks nothing
of the shape: every router encoder (S <= the kernel's ``MAX_SEQ``, any
head width; the published 12 heads of 64 and the reduced 4 of 8 both
compile for a v5e) takes the kernel on a TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.encoder_attention.kernel import encoder_attention_kernel


def uses_kernel(force_kernel: bool = False) -> bool:
    return force_kernel or jax.default_backend() == "tpu"


def encoder_attention(q, k, v, bias, wo):
    """q, k, v (B, S, H, Dh); bias (B, S) f32 over keys; wo (H, Dh, D)
    -> (B, S, D). One program per document, all its heads."""
    h, d = q.shape[2:]
    o = encoder_attention_kernel(q, k, v, bias,
                                 interpret=jax.default_backend() != "tpu")
    return jnp.einsum("bsm,md->bsd", o, wo.reshape(h * d, -1))
