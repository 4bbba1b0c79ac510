"""Host-numpy oracle for the fused prepare-stage routing inputs.

Operates on the packed token-stream batch (``ops.pack_routing_batch``)
and reproduces ``core.features.batch_fast_features`` +
``batch_first_page_tokens`` **bit-for-bit**: every per-document count
is an exact integer either way, and the float64 → float32 assembly
matches the legacy expressions term by term. This is both the parity
oracle the Pallas kernel is tested against (1e-6) and the CPU dispatch
path of ``routing_features`` — it works on the flat stream in O(T)
(bincount segment sums, like the legacy path) but swaps the legacy
O(T log T) composite-key sort for an O(T + n·V) presence bitmap and
fuses the first-page token/mask assembly into the same pass.

Takes plain numeric token-space parameters (no ``CorpusConfig``):
kernels must not depend on core — core imports kernels, not the
reverse.
"""
from __future__ import annotations

import numpy as np

N_FAST_FEATURES = 8
# beyond this many presence-bitmap cells, fall back to the sort-based
# distinct count (bitmap memory is n_docs * vocab_size bytes)
_BITMAP_CELL_BUDGET = 1 << 26


def _distinct_per_doc(flat, rows, n: int, vocab_size: int) -> np.ndarray:
    """Exact distinct-token count per document of the flat stream."""
    if n * int(vocab_size) <= _BITMAP_CELL_BUDGET:
        present = np.zeros((n, int(vocab_size)), np.bool_)
        present[rows, flat] = True
        return present.sum(axis=1)
    key = rows.astype(np.int64) * int(vocab_size) + flat   # legacy sort
    return np.bincount(np.unique(key) // int(vocab_size), minlength=n)


def routing_features_ref(flat, rows, starts, n_tok, first_len, n_pages,
                         n_empty, *, ws: int, scramble: int, mangled: int,
                         latex_lo: int, ident_lo: int, vocab_size: int,
                         max_len: int = 0, bos: int = 1):
    """Packed batch -> (fast (n, 8) f32[, toks (n, max_len) i32,
    mask (n, max_len) f32]).

    ``flat`` is the (T,) concatenation of every document's pages,
    ``rows`` the (T,) doc index per token, ``starts`` the (n,) stream
    start offsets. Token/mask outputs are produced iff ``max_len > 0``
    (the CLS-III LLM router variant); otherwise the return is
    ``(fast, None, None)``.
    """
    flat = np.asarray(flat)
    rows = np.asarray(rows)
    n = len(n_tok)
    n_tok = np.asarray(n_tok, np.int64)
    n_pages = np.asarray(n_pages, np.int64)
    n_empty = np.asarray(n_empty, np.int64)
    out = np.zeros((n, N_FAST_FEATURES), np.float32)
    if n:
        denom = np.maximum(n_tok.astype(np.float64), 1.0)

        def frac(mask):
            return np.bincount(rows[mask], minlength=n) / denom

        out[:, 0] = np.log1p(n_tok.astype(np.float64)) / 10.0
        out[:, 1] = frac(flat == ws)
        out[:, 2] = frac(flat == scramble)
        out[:, 3] = frac(flat == mangled)
        out[:, 4] = frac((flat >= latex_lo) & (flat < ident_lo))
        out[:, 5] = _distinct_per_doc(flat, rows, n, vocab_size) / denom
        out[:, 6] = n_empty / np.maximum(n_pages, 1)
        out[:, 7] = n_pages / 10.0
        # docs with no output at all keep the all-zero signature row
        out[n_tok == 0] = 0.0
    if not max_len:
        return out, None, None
    m = np.minimum(np.asarray(first_len, np.int64), max_len - 1)
    toks = np.zeros((n, max_len), np.int32)
    mask = np.zeros((n, max_len), np.float32)
    if n:
        toks[:, 0] = bos
        # gather each stream's head (= its first page, truncated) out of
        # the flat concatenation; clip keeps padded lanes in bounds
        head = np.asarray(starts, np.int64)[:, None] \
            + np.arange(max_len - 1)[None, :]
        vals = (flat[np.minimum(head, max(len(flat) - 1, 0))]
                if len(flat) else np.zeros((n, max_len - 1), np.int32))
        keep = np.arange(max_len - 1)[None, :] < m[:, None]
        toks[:, 1:] = np.where(keep, vals, 0)
        mask[np.arange(max_len)[None, :] < (m + 1)[:, None]] = 1.0
    return out, toks, mask
