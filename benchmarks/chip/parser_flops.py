"""Operations and bytes that the parser model's result needs, from its
widths (a configuration's ``parser_model``) and what it parsed: not
what today's code happens to do, so a faster design is credited
without a share passing 100%.

- ``encode_flops``: one page through the Swin encoder into the
  decoder's cross-attention keys and values, 2 per multiply-add: the
  patch embedding; per block the q/k/v and output projections, the
  scores and the weighted sum of each token over its window, and the
  MLP; each patch merging; then every decoder layer's key and value
  projections of the last stage. Norms, softmax and the bias are not
  counted.
- ``decode_bytes``: what greedy decoding must read, in ``param_dtype``
  bytes: per step the weights a step uses (every decoder layer's
  self-attention projections, the cross-attention query and output
  projections, the feed-forward pair, the biases and norms, and the
  tied output head) once for all pages; per live page and step the
  page's cross-attention keys and values in every layer; per live
  cached position the self-attention key and value in every layer.
  Slots without a live page, and positions past a page's own, are not
  counted.
"""
from __future__ import annotations


def _dtype_bytes(widths: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[widths["param_dtype"]]


def encode_flops(widths: dict) -> float:
    """Forward FLOPs of one page through the encoder and the cross-
    attention K/V projections."""
    w = widths
    h, wd = (s // w["patch"] for s in w["image_hw"])
    c = w["embed_dim"]
    n_win = w["window"] ** 2
    macs = h * wd * w["patch"] ** 2 * 3 * c
    for i, depth in enumerate(w["depths"]):
        n = h * wd
        per_block = n * (4 * c * c + 2 * w["mlp_ratio"] * c * c) \
            + 2 * n * n_win * c
        macs += depth * per_block
        if i < len(w["depths"]) - 1:
            h, wd = h // 2, wd // 2
            macs += h * wd * 4 * c * 2 * c
            c *= 2
    macs += w["dec_layers"] * 2 * h * wd * c * w["dec_d_model"]
    return 2.0 * macs


def decode_bytes(widths: dict, steps: float, live_slot_steps: float,
                 live_kv_positions: float) -> float:
    """Bytes greedy decoding must read over ``steps`` steps with
    ``live_slot_steps`` live (page, step) pairs whose cache lengths sum
    to ``live_kv_positions``."""
    w = widths
    d, f, L = w["dec_d_model"], w["dec_d_ff"], w["dec_layers"]
    n_enc = 1
    for s in w["image_hw"]:
        n_enc *= s // (w["patch"] * 2 ** (len(w["depths"]) - 1))
    per_layer = 6 * d * d + 6 * d + 2 * d * f + f + d + 6 * d
    weights = L * per_layer + w["vocab_size"] * d + 2 * d
    b = _dtype_bytes(w)
    return b * (steps * weights + live_slot_steps * 2 * L * n_enc * d
                + live_kv_positions * 2 * L * d)
