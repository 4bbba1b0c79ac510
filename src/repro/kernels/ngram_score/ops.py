"""Public fused n-gram BLEU op: the quality probe's scoring hot path.

``ngram_bleu(ref, hyp, ref_len, hyp_len)`` scores a padded (B, max_len)
batch of (reference, hypothesis) token streams per document. On TPU the
Pallas kernel keeps the pairwise equality matrices in VMEM; elsewhere it
dispatches to the sorted-multiset numpy oracle (ref.py), which is both
the exact float64 mirror of the host ``metrics.bleu`` rule and an
O(L log L) replacement for the old XLA O(L^2) pairwise path — the
``engine.score_kernel_speedup`` bench measures that win at probe batch
shapes. ``force_kernel`` runs the kernel in interpret mode (CI parity).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ngram_score.autotune import tuned_block_b
from repro.kernels.ngram_score.kernel import ngram_bleu_kernel
from repro.kernels.ngram_score.ref import ngram_bleu_ref


def ngram_bleu(ref, hyp, ref_len, hyp_len, *, max_n: int = 4,
               force_kernel: bool = False,
               block_b: int | None = None) -> np.ndarray:
    """ref, hyp: (B, max_len) padded int id arrays; ref_len, hyp_len:
    (B,) true lengths. Returns (B,) float64 per-document BLEU.
    ``block_b=None`` consults the per-shape autotune cache/store on the
    kernel path (default: eight docs per program for untuned shapes)."""
    ref = np.asarray(ref)
    hyp = np.asarray(hyp)
    if ref.shape != hyp.shape or ref.ndim != 2:
        raise ValueError(f"ngram_bleu needs matching (B, max_len) ref/hyp "
                         f"batches (got {ref.shape} vs {hyp.shape})")
    if force_kernel or jax.default_backend() == "tpu":
        if block_b is None:
            block_b = tuned_block_b(ref.shape[0], ref.shape[1], max_n)
        out = ngram_bleu_kernel(
            jnp.asarray(ref, jnp.int32), jnp.asarray(hyp, jnp.int32),
            jnp.asarray(ref_len, jnp.int32), jnp.asarray(hyp_len, jnp.int32),
            max_len=ref.shape[1], max_n=max_n,
            interpret=jax.default_backend() != "tpu", block_b=block_b)
        return np.asarray(out, np.float64)
    return ngram_bleu_ref(ref, hyp, np.asarray(ref_len),
                          np.asarray(hyp_len), max_n=max_n)
