"""Host oracle for the fused n-gram BLEU scorer: exact float64 mirror.

Same scoring rule as ``metrics.bleu`` (uniform n<=max_n weights, brevity
penalty, 1e-9 smoothing) on padded (B, max_len) id batches with length
masks, and the parity reference for the Pallas kernel. Clipped counts
come from sorted n-gram multisets instead of the kernel's O(L^2)
pairwise equality matrices — different factorization, identical counts.

The whole batch is counted at once with *dense integer gram ids*: an
n-gram's id extends the (n-1)-gram's compacted id by the next token's
compacted id, with the document id folded into the chain at order 1.
One int64 ``np.argsort`` per order over the valid positions of every
document (hyp and ref streams together) then yields everything at
once — run boundaries in the sorted values delimit the (doc, gram)
groups, per-stream bincounts over the group ranks give the clipped
counts, and the ranks scattered back are the dense ids the next order
extends. ~1 argsort per order over ~2·B·L elements total, instead of
byte-wise void sorts per document. This is the CPU dispatch target of
``ops.ngram_bleu``.

``_doc_bleu`` keeps the simple one-document factorization as the
oracle's oracle (tests pit the batched counts against it).
"""
from __future__ import annotations

import numpy as np

SMOOTH = 1e-9


def _gram_view(seq: np.ndarray, n: int) -> np.ndarray:
    """All n-gram windows of ``seq`` as one void element per gram, so a
    single sort/unique over opaque bytes counts the multiset."""
    win = np.ascontiguousarray(
        np.lib.stride_tricks.sliding_window_view(seq, n))
    return win.view(np.dtype((np.void, win.dtype.itemsize * n))).ravel()


def _doc_bleu(ref: np.ndarray, hyp: np.ndarray, max_n: int) -> float:
    """One document, the straightforward per-doc factorization."""
    lh = len(hyp)
    if lh == 0:
        return 0.0
    log_p = 0.0
    for n in range(1, max_n + 1):
        total = max(lh - n + 1, 0)
        clipped = 0
        if total > 0 and len(ref) >= n:
            uh, ch = np.unique(_gram_view(hyp, n), return_counts=True)
            ur, cr = np.unique(_gram_view(ref, n), return_counts=True)
            _, ih, ir = np.intersect1d(uh, ur, assume_unique=True,
                                       return_indices=True)
            clipped = int(np.minimum(ch[ih], cr[ir]).sum())
        log_p += np.log((clipped + SMOOTH) / max(total, 1))
    log_p /= max_n
    bp = min(1.0, np.exp(1.0 - len(ref) / max(lh, 1)))
    return float(bp * np.exp(log_p))


def ngram_bleu_ref(ref: np.ndarray, hyp: np.ndarray, ref_len: np.ndarray,
                   hyp_len: np.ndarray, *, max_n: int = 4) -> np.ndarray:
    """Per-document BLEU over a padded batch.

    ref, hyp: (B, max_len) int id arrays (padding beyond the lengths is
    ignored); ref_len, hyp_len: (B,) true lengths. Returns (B,) float64.
    """
    ref = np.ascontiguousarray(ref)
    hyp = np.ascontiguousarray(hyp)
    lr = np.asarray(ref_len, np.int64)
    lh = np.asarray(hyp_len, np.int64)
    b, max_len = ref.shape
    lens = np.concatenate([lh, lr])            # rows 0..b-1 hyp, b.. ref

    # order-1 compacted token ids over every position of both streams
    # (padding garbage compacts too; it is masked out before counting
    # and, because valid positions shrink with the order, a padded id
    # can never leak into a later order's extension). T = id count.
    both = np.concatenate([hyp, ref], 0).astype(np.int64, copy=False)
    u0 = np.unique(both)
    tok1 = np.searchsorted(u0, both.ravel()).reshape(2 * b, max_len)
    t_ids = np.int64(len(u0))
    doc2 = np.broadcast_to((np.arange(2 * b) % b)[:, None],
                           (2 * b, max_len))
    is_ref2 = np.broadcast_to((np.arange(2 * b) >= b)[:, None],
                              (2 * b, max_len))
    g = doc2 * t_ids + tok1
    log_p = np.zeros(b, np.float64)
    for n in range(1, max_n + 1):
        w = max_len - n + 1
        total = np.maximum(lh - n + 1, 0)
        if w <= 0:                     # max_len < n: no grams anywhere
            log_p += np.log(SMOOTH / np.maximum(total, 1))
            continue
        if n > 1:
            # extend the (doc, (n-1)-gram) id at position p by the
            # token at p+n-1; ids stay < 2*b*max_len and t_ids <=
            # 2*b*max_len, so the product never overflows int64
            g = g[:, :w] * t_ids + tok1[:, n - 1:]
        valid = np.arange(w)[None, :] < (lens[:, None] - n + 1)
        vals = g[valid]
        if vals.size == 0:             # every document shorter than n
            log_p += np.log(SMOOTH / np.maximum(total, 1))
            continue                   # valid only shrinks: g is moot
        # ONE argsort: runs of equal sorted values are the (doc, gram)
        # multiset entries of both streams at once (stability is
        # irrelevant — group identity and counts are order-free)
        order = np.argsort(vals)
        s = vals[order]
        new = np.empty(s.size, np.bool_)
        new[0] = True
        np.not_equal(s[1:], s[:-1], out=new[1:])
        grp = np.cumsum(new) - 1       # dense group rank per element
        n_grp = int(grp[-1]) + 1
        fr = is_ref2[:, :w][valid][order]
        cr = np.bincount(grp[fr], minlength=n_grp)
        ch = np.bincount(grp[~fr], minlength=n_grp)
        docg = doc2[:, :w][valid][order[new]]   # one doc id per group
        clipped = np.bincount(docg, weights=np.minimum(ch, cr),
                              minlength=b)
        log_p += np.log((clipped + SMOOTH) / np.maximum(total, 1))
        if n < max_n:
            # the group rank doubles as the next order's dense id
            ids = np.empty(s.size, np.int64)
            ids[order] = grp
            nxt = np.zeros((2 * b, w), np.int64)
            nxt[valid] = ids
            g = nxt
    log_p /= max_n
    bp = np.minimum(1.0, np.exp(1.0 - lr / np.maximum(lh, 1)))
    return np.where(lh > 0, bp * np.exp(log_p), 0.0)
