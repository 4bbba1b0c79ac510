"""Operations and bytes that each measured computation's result needs,
from the inputs' shapes and lengths: not what today's code happens to
do. A faster algorithm is credited without a share passing 100%.

- ``encoder_flops``: a BERT-style encoder forward over each document's
  real (unmasked) tokens: the four attention projections and the
  feed-forward pair (2 per multiply-add), the attention scores and the
  weighted sum over real tokens, then the pooler and the head on the
  first position. Embedding lookups, norms and activations are not
  counted.
- ``fast_features_work``: read every real token once, read four int32
  scalars per document, write eight float32 features per document and,
  for the llm router, the int32 tokens and float32 mask of
  ``max_len`` positions; ``FEATURE_OPS_PER_TOKEN`` operations per
  token (four class tests, four counts, a distinct-token test and
  its count).
- ``ngram_work``: read the real tokens of reference and hypothesis
  (at most ``max_len`` each), two int32 lengths per document and write
  one float32 score; ``NGRAM_OPS_PER_TOKEN`` operations per token and
  n-gram order (a gram hash, a count in each stream, a clipped minimum).
"""
from __future__ import annotations

import numpy as np

FEATURE_OPS_PER_TOKEN = 10
NGRAM_OPS_PER_TOKEN = 4


def encoder_flops(real_tokens, enc: dict) -> float:
    """Forward FLOPs over documents with ``real_tokens`` unmasked
    positions each."""
    n = np.asarray(real_tokens, np.float64)
    d, f, L = enc["d_model"], enc["d_ff"], enc["n_layers"]
    per_token = L * 2 * (4 * d * d + 2 * d * f)
    attention = L * 2 * 2 * n * n * d
    head = 2 * d * d + 2 * d * enc["n_outputs"]
    return float(np.sum(per_token * n + attention + head))


def fast_features_work(stream_tokens, max_len: int) -> tuple[float, float]:
    """(operations, bytes) of one prepare call over documents with
    ``stream_tokens`` extracted tokens each."""
    t = np.asarray(stream_tokens, np.float64)
    n = len(t)
    moved = 4 * t.sum() + 16 * n + 32 * n + (8 * n * max_len)
    return FEATURE_OPS_PER_TOKEN * float(t.sum()), float(moved)


def ngram_work(ref_tokens, hyp_tokens, max_n: int = 4
               ) -> tuple[float, float]:
    """(operations, bytes) of scoring documents whose reference and
    hypothesis have ``ref_tokens`` and ``hyp_tokens`` scored tokens."""
    r = np.asarray(ref_tokens, np.float64)
    h = np.asarray(hyp_tokens, np.float64)
    ops = NGRAM_OPS_PER_TOKEN * max_n * float(r.sum() + h.sum())
    moved = 4 * float(r.sum() + h.sum()) + 8 * len(r) + 4 * len(r)
    return ops, moved


def roofline_share(ops: float, moved: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """(percent of the least time the chip could take, which bound sets
    that time) for work done in ``seconds`` of kernel time."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = moved / peak["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
