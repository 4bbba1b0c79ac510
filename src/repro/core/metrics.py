"""Parser-output quality metrics (§2.2, §7.2).

Texts are token-id sequences (numpy int arrays). Metrics:

- BLEU      — corpus/doc n-gram precision (n<=4), brevity penalty.
- ROUGE-L   — LCS-based F-measure (jit-compiled DP, vmapped over pages).
- CAR       — character accuracy rate ~ 1 - normalized word-level
              Levenshtein, weighted by per-token character length.
- coverage  — fraction of reference pages with any matching output.
- AT        — accepted tokens: fraction of tokens in documents whose BLEU
              exceeds a threshold (the paper's goodput numerator).

``score_batch`` is the vectorized per-document front door: all three
hypothesis-vs-reference scorers run over one padded (B, max_len) batch
with length masks — the hot path of the online quality probe
(core/quality), which scores sampled campaign batches at round
granularity. BLEU dispatches through the fused n-gram op
(kernels/ngram_score: Pallas equality-matrix kernel on TPU, sorted
n-gram multisets on CPU). ``rouge_l`` and ``car`` are thin corpus-mean
wrappers over it.
"""
from __future__ import annotations

import functools
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ngram_score.ops import ngram_bleu

# ---------------------------------------------------------------------------
# BLEU
# ---------------------------------------------------------------------------


def _ngram_counts(seq: np.ndarray, n: int) -> Counter:
    if len(seq) < n:
        return Counter()
    view = np.lib.stride_tricks.sliding_window_view(seq, n)
    return Counter(map(tuple, view))


def bleu(ref: np.ndarray, hyp: np.ndarray, max_n: int = 4,
         smooth: float = 1e-9) -> float:
    """Sentence/document BLEU with uniform weights and brevity penalty."""
    ref = np.asarray(ref).ravel()
    hyp = np.asarray(hyp).ravel()
    if len(hyp) == 0:
        return 0.0
    log_p = 0.0
    for n in range(1, max_n + 1):
        rc, hc = _ngram_counts(ref, n), _ngram_counts(hyp, n)
        total = max(sum(hc.values()), 1)
        clipped = sum(min(c, rc[g]) for g, c in hc.items())
        log_p += np.log((clipped + smooth) / total)
    log_p /= max_n
    bp = min(1.0, np.exp(1.0 - len(ref) / max(len(hyp), 1)))
    return float(bp * np.exp(log_p))


def corpus_bleu(refs: list[np.ndarray], hyps: list[np.ndarray],
                max_n: int = 4) -> float:
    """Corpus BLEU (pooled n-gram counts, standard Papineni definition)."""
    tot_clip = np.zeros(max_n)
    tot = np.zeros(max_n)
    ref_len = hyp_len = 0
    for ref, hyp in zip(refs, hyps):
        ref = np.asarray(ref).ravel()
        hyp = np.asarray(hyp).ravel()
        ref_len += len(ref)
        hyp_len += len(hyp)
        for n in range(1, max_n + 1):
            rc, hc = _ngram_counts(ref, n), _ngram_counts(hyp, n)
            tot[n - 1] += sum(hc.values())
            tot_clip[n - 1] += sum(min(c, rc[g]) for g, c in hc.items())
    if hyp_len == 0:
        return 0.0
    log_p = np.mean(np.log((tot_clip + 1e-9) / np.maximum(tot, 1)))
    bp = min(1.0, np.exp(1.0 - ref_len / max(hyp_len, 1)))
    return float(bp * np.exp(log_p))


# ---------------------------------------------------------------------------
# LCS (ROUGE-L) and Levenshtein (CAR) — jitted DPs
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_len",))
def _lcs_batch(a: jax.Array, b: jax.Array, la: jax.Array, lb: jax.Array,
               max_len: int) -> jax.Array:
    """Batched LCS length. a, b: (B, max_len) padded; la, lb true lengths."""

    def one(a1, b1, la1, lb1):
        valid_b = jnp.arange(max_len) < lb1

        def row(prev, ai):
            i, prev_row = prev
            match = (b1 == ai) & valid_b & (i < la1)
            # new[j] = max(prev_row[j], new[j-1], prev_row[j-1] + match)

            def cell(carry, inp):
                diag, pj, m = inp
                best = jnp.maximum(pj, jnp.maximum(carry, diag + m))
                return best, best

            diag = jnp.concatenate([jnp.zeros(1, jnp.int32), prev_row[:-1]])
            _, new_row = jax.lax.scan(
                cell, jnp.int32(0), (diag, prev_row, match.astype(jnp.int32)))
            return (i + 1, new_row), None

        (_, last), _ = jax.lax.scan(row, (jnp.int32(0),
                                          jnp.zeros(max_len, jnp.int32)), a1)
        return last[jnp.maximum(lb1 - 1, 0)] * (lb1 > 0)

    return jax.vmap(one)(a, b, la, lb)


@functools.partial(jax.jit, static_argnames=("max_len",))
def _edit_distance_batch(a: jax.Array, b: jax.Array, la: jax.Array,
                         lb: jax.Array, max_len: int) -> jax.Array:
    """Batched word-level Levenshtein distance on padded id sequences."""

    def one(a1, b1, la1, lb1):
        init = jnp.minimum(jnp.arange(1, max_len + 1), lb1).astype(jnp.int32)

        def row(carry, inp):
            i, prev_row = carry
            ai = inp
            active = i < la1
            sub = (b1 != ai).astype(jnp.int32)
            diag = jnp.concatenate([jnp.array([0], jnp.int32) + i,
                                    prev_row[:-1]])

            def cell(left, inp2):
                up, dg, s = inp2
                best = jnp.minimum(jnp.minimum(up + 1, left + 1), dg + s)
                return best, best

            _, new_row = jax.lax.scan(cell, i + 1, (prev_row, diag, sub))
            new_row = jnp.where(active, new_row, prev_row)
            return (i + 1, new_row), None

        (_, last), _ = jax.lax.scan(row, (jnp.int32(0), init), a1)
        return last[jnp.maximum(lb1 - 1, 0)] * (lb1 > 0) + \
            jnp.where(lb1 > 0, 0, la1)

    return jax.vmap(one)(a, b, la, lb)


def _pad_batch(seqs: list[np.ndarray], max_len: int):
    arr = np.zeros((len(seqs), max_len), np.int32) - 1
    lens = np.zeros(len(seqs), np.int32)
    for i, s in enumerate(seqs):
        s = np.asarray(s).ravel()[:max_len]
        arr[i, :len(s)] = s
        lens[i] = len(s)
    return arr, lens


SCORE_METRICS = ("bleu", "rouge", "car")


def score_batch(refs: list[np.ndarray], hyps: list[np.ndarray],
                max_len: int = 512, beta: float = 1.2,
                metrics: tuple[str, ...] = SCORE_METRICS
                ) -> dict[str, np.ndarray]:
    """Vectorized per-document scores for a batch of (reference,
    hypothesis) token streams — the quality probe's hot path.

    Every sequence is truncated/padded to ``max_len`` and scored with
    length masks: BLEU by the fused n-gram op (``ngram_bleu``), ROUGE-L
    and CAR by the jitted batched DPs (``_lcs_batch``,
    ``_edit_distance_batch``); an empty hypothesis scores 0 on every
    metric. The batch dimension is padded to the next
    power of two (zero-length rows, sliced off before returning) so the
    jit caches stay bounded however probe sample sizes vary.

    Returns ``{"bleu"|"rouge"|"car": (n,), "ref_len": (n,),
    "hyp_len": (n,)}`` float64 arrays, restricted to ``metrics``.
    """
    if len(refs) != len(hyps):
        raise ValueError(f"score_batch needs one hypothesis per reference "
                         f"(got {len(refs)} refs, {len(hyps)} hyps)")
    bad = [m for m in metrics if m not in SCORE_METRICS]
    if bad:
        raise ValueError(f"unknown score metrics {bad}; "
                         f"choose from {SCORE_METRICS}")
    n = len(refs)
    if n == 0:
        out = {m: np.zeros(0) for m in metrics}
        out["ref_len"] = np.zeros(0)
        out["hyp_len"] = np.zeros(0)
        return out
    n_pad = 1 << (n - 1).bit_length()
    fill = [np.zeros(0, np.int32)] * (n_pad - n)
    ra, rl = _pad_batch(list(refs) + fill, max_len)
    ha, hl = _pad_batch(list(hyps) + fill, max_len)
    rln = rl.astype(np.float64)[:n]
    hln = hl.astype(np.float64)[:n]
    out: dict[str, np.ndarray] = {}
    if "bleu" in metrics:
        out["bleu"] = ngram_bleu(ra, ha, rl, hl)[:n]
    if "rouge" in metrics:
        lcs = np.asarray(_lcs_batch(jnp.asarray(ra), jnp.asarray(ha),
                                    jnp.asarray(rl), jnp.asarray(hl),
                                    max_len), np.float64)[:n]
        p = lcs / np.maximum(hln, 1)
        r = lcs / np.maximum(rln, 1)
        out["rouge"] = ((1 + beta ** 2) * p * r
                        / np.maximum(r + beta ** 2 * p, 1e-9))
    if "car" in metrics:
        dist = np.asarray(_edit_distance_batch(jnp.asarray(ra),
                                               jnp.asarray(ha),
                                               jnp.asarray(rl),
                                               jnp.asarray(hl),
                                               max_len), np.float64)[:n]
        out["car"] = np.clip(1.0 - dist / np.maximum(rln, 1), 0.0, 1.0)
    out["ref_len"] = rln
    out["hyp_len"] = hln
    return out


def rouge_l(refs: list[np.ndarray], hyps: list[np.ndarray],
            max_len: int = 512, beta: float = 1.2) -> float:
    """Mean ROUGE-L F score over documents (truncated to max_len tokens)."""
    return float(np.mean(score_batch(refs, hyps, max_len, beta,
                                     metrics=("rouge",))["rouge"]))


def car(refs: list[np.ndarray], hyps: list[np.ndarray],
        max_len: int = 512, mean_word_chars: float = 5.0) -> float:
    """Character accuracy rate ≈ 1 - char-edit/chars, where word-level
    edits are weighted by mean word length (substituted words cost a full
    word of characters; the id->charseq map is deterministic so this is a
    tight proxy)."""
    return float(np.mean(score_batch(refs, hyps, max_len,
                                     metrics=("car",))["car"]))


# ---------------------------------------------------------------------------
# Document-level aggregates
# ---------------------------------------------------------------------------


def coverage(ref_pages: list[list[np.ndarray]],
             hyp_pages: list[list[np.ndarray]]) -> float:
    """Fraction of reference pages retrieved (non-empty parser output)."""
    total = got = 0
    for rp, hp in zip(ref_pages, hyp_pages):
        total += len(rp)
        got += sum(1 for i in range(len(rp))
                   if i < len(hp) and len(np.asarray(hp[i]).ravel()) > 0)
    return got / max(total, 1)


def accepted_tokens(refs: list[np.ndarray], hyps: list[np.ndarray],
                    doc_bleus: list[float] | None = None,
                    threshold: float = 0.4) -> float:
    """AT: fraction of (reference) tokens living in documents whose BLEU
    exceeds the acceptance threshold."""
    if doc_bleus is None:
        doc_bleus = [bleu(r, h) for r, h in zip(refs, hyps)]
    tok = np.array([len(np.asarray(r).ravel()) for r in refs], np.float64)
    ok = np.array([b > threshold for b in doc_bleus], np.float64)
    return float((tok * ok).sum() / max(tok.sum(), 1))


def evaluate_parser(refs: list[np.ndarray], hyps: list[np.ndarray],
                    ref_pages=None, hyp_pages=None,
                    at_threshold: float = 0.4) -> dict:
    doc_bleus = [bleu(r, h) for r, h in zip(refs, hyps)]
    out = {
        "bleu": float(np.mean(doc_bleus)),
        "rouge": rouge_l(refs, hyps),
        "car": car(refs, hyps),
        "at": accepted_tokens(refs, hyps, doc_bleus, at_threshold),
    }
    if ref_pages is not None:
        out["coverage"] = coverage(ref_pages, hyp_pages)
    return out
