"""Plain reference of what one engine batch must produce.

Written from the semantics the system states, independent of its code:
nothing here imports the program. Each function is the straightforward
form of one stage, so a cell's ``correct`` compares the timed path
against it stage by stage (see ``check.py``):

- ``batch_rng``: the per-batch random stream, keyed by (engine seed,
  batch key), that the cheap parse draws first and the expensive
  re-parse continues.
- ``channel``: a parser's corruption channel over one batch (the
  parsers' severity profiles are copied below), with the draws in the
  order the channel states: page drops, LaTeX, identifiers,
  substitutions, near-word edits, scrambles, whitespace.
- ``fast_features`` / ``first_page``: the eight CLS-I features and the
  encoder's first-page tokens and mask, one document at a time.
- ``select``: route the floor(alpha*k) highest positive improvements,
  ties in row order.
- ``bleu``: document BLEU (4-grams, uniform weights, 1e-9 smoothing,
  brevity penalty) of the first ``max_len`` tokens, as the probe scores.

``precision="control"`` computes the float stages one precision lower
than the configuration states (bfloat16 for float32 features and
scores); it is the control that a cell's limits must reject.
"""
from __future__ import annotations

import math
from collections import Counter

import numpy as np

PAD, BOS, WS, SCRAMBLE, MANGLED = 0, 1, 2, 3, 4
WORD_LO = 10
N_FEATURES = 8
#: improvement given to a document CLS-I calls invalid (must re-parse)
CLS1_OVERRIDE = 1e3
#: only strictly positive predicted improvements are ever routed
POSITIVE_TAU = 1e-12

#: per-parser corruption severities (rates at difficulty 1)
PROFILES = {
    "pymupdf": dict(p_ws=0.10, p_sub=0.08, p_scramble=0.45, p_char=0.12,
                    p_latex=0.85, p_ident=0.3, p_page_drop=0.085, p_fail=0.0,
                    difficulty_power=3.0, flat_floor=0.13, text_layer=True),
    "nougat": dict(p_ws=0.0, p_sub=0.17, p_scramble=0.0, p_char=0.10,
                   p_latex=0.10, p_ident=0.12, p_page_drop=0.07, p_fail=0.0,
                   difficulty_power=1.0, flat_floor=0.52, text_layer=False),
}


def batch_rng(seed: int, key: int) -> np.random.RandomState:
    """The batch's stream: (seed, key) mixed into a 32-bit state."""
    x = (seed * 0x9E3779B1 + key * 0x85EBCA77) & 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x7FEB352D) & 0xFFFFFFFF
    x ^= x >> 15
    return np.random.RandomState(x or 1)


def channel(docs, parser: str, corpus: dict, rng) -> list[list[np.ndarray]]:
    """A parser's output pages for each document of the batch. Every
    draw is over the batch's whole token stream, in the stated order."""
    prof = PROFILES[parser]
    if not docs:
        return []
    sev = np.array([prof["flat_floor"] + d.difficulty
                    ** prof["difficulty_power"] for d in docs])
    if prof["text_layer"]:
        sev = np.where([d.scanned for d in docs], np.minimum(1.0, sev + 0.35),
                       sev)
    failed = (rng.rand(len(docs)) < prof["p_fail"] * sev if prof["p_fail"]
              else np.zeros(len(docs), bool))
    pages = [(i, pg) for i, d in enumerate(docs) for pg in d.pages]
    dropped = (rng.rand(len(pages)) < prof["p_page_drop"]
               if prof["p_page_drop"] else np.zeros(len(pages), bool))
    dropped |= failed[[i for i, _ in pages]]
    toks = np.concatenate([pg for _, pg in pages]).astype(np.int64)
    n = len(toks)
    page_of = np.repeat(np.arange(len(pages)), [len(pg) for _, pg in pages])
    s = sev[np.array([i for i, _ in pages])[page_of]]
    latex_lo = WORD_LO + corpus["n_words"]
    ident_lo = latex_lo + corpus["n_latex"]
    is_latex = (toks >= latex_lo) & (toks < ident_lo)
    is_ident = toks >= ident_lo
    if prof["p_latex"]:
        hit = rng.rand(n) < prof["p_latex"] * (0.3 + 0.7 * s)
        toks[is_latex & hit] = MANGLED
    if prof["p_ident"]:
        hit = rng.rand(n) < prof["p_ident"] * (0.3 + 0.7 * s)
        toks[is_ident & hit] = MANGLED
    if prof["p_sub"]:
        hit = rng.rand(n) < prof["p_sub"] * s
        repl = rng.randint(WORD_LO, WORD_LO + corpus["n_words"], size=n)
        toks[hit] = repl[hit]
    if prof["p_char"]:
        hit = (rng.rand(n) < prof["p_char"] * s) & (toks >= WORD_LO)
        toks[hit] ^= 1
    if prof["p_scramble"]:
        toks[rng.rand(n) < prof["p_scramble"] * s] = SCRAMBLE
    insert = (rng.rand(n) < prof["p_ws"] * s if prof["p_ws"]
              else np.zeros(n, bool))
    out = [[] for _ in docs]
    for p, (i, _) in enumerate(pages):
        seg = page_of == p
        t, ins = toks[seg], insert[seg]
        page = []
        for tok, before in zip(t.tolist(), ins.tolist()):
            if before:
                page.append(WS)
            page.append(tok)
        out[i].append(np.zeros(0, np.int32) if dropped[p]
                      else np.array(page, np.int32))
    return out


def fast_features(pages: list[np.ndarray], corpus: dict,
                  precision: str = "exact") -> np.ndarray:
    """CLS-I features of one document's extracted pages (float64; the
    control rounds every step to bfloat16)."""
    rnd = _rounder(precision)
    stream = [t for pg in pages for t in pg.tolist()]
    n = len(stream)
    if n == 0:
        return np.zeros(N_FEATURES)
    latex_lo = WORD_LO + corpus["n_words"]
    ident_lo = latex_lo + corpus["n_latex"]
    count = {WS: 0, SCRAMBLE: 0, MANGLED: 0}
    latex = 0
    for t in stream:
        if t in count:
            count[t] += 1
        latex += latex_lo <= t < ident_lo
    empty = sum(len(pg) == 0 for pg in pages)
    n_pages = len(pages)
    return np.array([
        rnd(rnd(math.log(rnd(n + 1.0))) / 10.0),
        rnd(count[WS] / rnd(n)), rnd(count[SCRAMBLE] / rnd(n)),
        rnd(count[MANGLED] / rnd(n)), rnd(latex / rnd(n)),
        rnd(len(set(stream)) / rnd(n)), rnd(empty / max(n_pages, 1)),
        rnd(n_pages / 10.0)])


def first_page(pages: list[np.ndarray], max_len: int):
    """BOS, then the first page truncated to ``max_len - 1``; the mask
    covers BOS and the page."""
    head = pages[0][:max_len - 1] if pages and len(pages[0]) else []
    toks = np.zeros(max_len, np.int32)
    toks[0] = BOS
    toks[1:1 + len(head)] = head
    mask = np.zeros(max_len, np.float32)
    mask[:1 + len(head)] = 1.0
    return toks, mask


def metadata(doc) -> np.ndarray:
    """CLS-II features: producer and publisher one-hot, scaled year,
    pages, scanned."""
    producers = ("pdflatex", "msword", "scanner-v1", "scanner-v2",
                 "indesign", "unknown")
    publishers = ("ArXiv", "BioRxiv", "BMC", "MDPI", "MedRxiv", "Nature")
    v = np.zeros(len(producers) + len(publishers) + 3, np.float32)
    v[producers.index(doc.producer)] = 1.0
    v[len(producers) + publishers.index(doc.publisher)] = 1.0
    v[-3:] = [(doc.year - 2000) / 25.0, doc.n_pages / 10.0,
              float(doc.scanned)]
    return v


def logistic(x: np.ndarray, w: np.ndarray, b: float) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ w + b)))


def fit_logistic(x: np.ndarray, y: np.ndarray, steps: int = 300,
                 lr: float = 0.5, l2: float = 1e-4):
    """Full-batch gradient descent on the logistic loss -> (w, b)."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    w, b = np.zeros(x.shape[1]), 0.0
    for _ in range(steps):
        g = 1.0 / (1.0 + np.exp(-(x @ w + b))) - y
        w -= lr * (x.T @ g / len(y) + l2 * w)
        b -= lr * float(g.mean())
    return w, b


def capacity(alpha: float, k: int) -> int:
    """floor(alpha * k), with a product within 1e-9 of an integer taken
    as that integer."""
    v = alpha * k
    if abs(v - round(v)) <= 1e-9 * max(abs(v), 1.0):
        v = round(v)
    return max(min(int(v), k), 0)


def select(improvement: np.ndarray, alpha: float) -> np.ndarray:
    """Rows routed to the expensive parser, ascending: the capacity
    highest scores that are at least the capacity-th largest and
    positive, ties taken in row order."""
    k = len(improvement)
    cap = capacity(alpha, k)
    if cap == 0:
        return np.zeros(0, np.int64)
    order = sorted(range(k), key=lambda i: (-float(improvement[i]), i))
    tau = max(float(improvement[order[cap - 1]]), POSITIVE_TAU)
    return np.array(sorted(i for i in order[:cap]
                           if float(improvement[i]) >= tau), np.int64)


def bleu(ref: np.ndarray, hyp: np.ndarray, max_len: int,
         precision: str = "exact", max_n: int = 4) -> float:
    """BLEU of the first ``max_len`` tokens of hypothesis and reference;
    an empty hypothesis scores 0."""
    rnd = _rounder(precision)
    ref = [int(t) for t in np.asarray(ref).ravel()[:max_len]]
    hyp = [int(t) for t in np.asarray(hyp).ravel()[:max_len]]
    if not hyp:
        return 0.0
    log_p = 0.0
    for n in range(1, max_n + 1):
        rc = Counter(tuple(ref[i:i + n]) for i in range(len(ref) - n + 1))
        hc = Counter(tuple(hyp[i:i + n]) for i in range(len(hyp) - n + 1))
        total = max(sum(hc.values()), 1)
        clipped = sum(min(c, rc[g]) for g, c in hc.items())
        log_p = rnd(log_p + rnd(math.log(rnd((clipped + 1e-9) / total))))
    log_p = rnd(log_p / max_n)
    bp = min(1.0, rnd(math.exp(rnd(1.0 - len(ref) / max(len(hyp), 1)))))
    return rnd(bp * rnd(math.exp(log_p)))


def probed(seed: int, key: int, rate: float) -> bool:
    """Whether the quality probe samples batch ``key``."""
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return bool(batch_rng(seed, key).rand() < rate)


def _rounder(precision: str):
    if precision == "exact":
        return float
    if precision == "control":
        import ml_dtypes

        return lambda v: float(np.asarray(v, ml_dtypes.bfloat16))
    raise ValueError(f"unknown precision {precision!r}")
