"""The one traffic generator: a traffic file's parameters -> the cell's
document pool and its endless stream of route batches.

The document sampling follows the synthetic corpus the system was
built against (``data/synthetic.generate_corpus``: Zipf words,
LaTeX spans and identifiers, difficulty from scan, producer, LaTeX and
age), copied here so that a change to the program cannot move the
yardstick. Two things differ on purpose, so that every seed does the
same work in another order: the page counts are the traffic file's
range repeated evenly and shuffled, and exactly ``scanned_share`` of
the documents are scans.
"""
from __future__ import annotations

import numpy as np

CATEGORIES = ("math", "bio", "chem", "phys", "eng", "med", "econ", "cs")
PUBLISHERS = ("ArXiv", "BioRxiv", "BMC", "MDPI", "MedRxiv", "Nature")
BORN_DIGITAL = (("pdflatex", "msword", "indesign", "unknown"),
                (0.5, 0.25, 0.15, 0.1))
SCANNERS = ("scanner-v1", "scanner-v2")
WORD_LO = 10


def stream_seed(seed: int, salt: int) -> int:
    """A 32-bit seed for one named stream of the run."""
    x = (seed * 0x9E3779B1 + salt * 0xC2B2AE3D) & 0xFFFFFFFF
    x ^= x >> 16
    return (x * 0x7FEB352D) & 0xFFFFFFFF


def make_pool(traffic: dict, corpus: dict, seed: int, document_cls,
              n_docs: int | None = None) -> list:
    """``n_docs`` (default ``traffic["pool_docs"]``) documents drawn from
    ``seed``; ``document_cls`` is the system's document record."""
    n = int(n_docs or traffic["pool_docs"])
    rng = np.random.RandomState(stream_seed(seed, 1))
    lo, hi = traffic["min_pages"], traffic["max_pages"]
    n_pages = np.resize(np.arange(lo, hi + 1), n)
    rng.shuffle(n_pages)
    scanned = np.arange(n) < round(traffic["scanned_share"] * n)
    rng.shuffle(scanned)
    spread = traffic["page_token_spread"]
    page_len = (traffic["page_tokens"]
                * rng.uniform(1 - spread, 1 + spread, int(n_pages.sum()))
                ).astype(np.int64)
    ranks = np.arange(1, corpus["n_words"] + 1)
    cdf = np.cumsum(1.0 / ranks ** 1.1)
    cdf /= cdf[-1]
    words = (np.minimum(np.searchsorted(cdf, rng.rand(int(page_len.sum()))),
                        corpus["n_words"] - 1) + WORD_LO).astype(np.int32)
    latex_lo = WORD_LO + corpus["n_words"]
    ident_lo = latex_lo + corpus["n_latex"]
    docs, p, t = [], 0, 0
    for i in range(n):
        category = CATEGORIES[rng.randint(len(CATEGORIES))]
        publisher = PUBLISHERS[rng.randint(len(PUBLISHERS))]
        latex_density = float(np.clip(
            rng.beta(1.2, 6.0)
            + (0.15 if category in ("math", "phys", "cs") else 0.0), 0, 0.5))
        year = int(1990 + 35 * rng.beta(3, 1.2))
        producer = (SCANNERS[rng.randint(2)] if scanned[i] else
                    str(rng.choice(BORN_DIGITAL[0], p=BORN_DIGITAL[1])))
        difficulty = float(np.clip(
            rng.beta(2.0, 5.0) + 0.45 * scanned[i]
            + 0.15 * (producer == "msword") + 0.2 * latex_density
            + 0.1 * (year < 2005), 0, 1))
        pages = []
        for _ in range(n_pages[i]):
            page = words[t:t + page_len[p]].copy()
            t += page_len[p]
            p += 1
            for _ in range(rng.poisson(latex_density * 8)):
                s = rng.randint(0, max(len(page) - 6, 1))
                ln = len(page[s:s + rng.randint(2, 6)])
                page[s:s + ln] = latex_lo + rng.randint(0, corpus["n_latex"],
                                                        ln)
            if category in ("chem", "bio", "med") and rng.rand() < 0.3:
                s = rng.randint(0, max(len(page) - 3, 1))
                ln = len(page[s:s + 2])
                page[s:s + ln] = ident_lo + rng.randint(0, corpus["n_ident"],
                                                        ln)
            pages.append(page)
        docs.append(document_cls(i, pages, difficulty, latex_density,
                                 producer, publisher, category, year,
                                 bool(scanned[i])))
    return docs


def batches(pool: list, batch_size: int, seed: int, first_key: int = 0):
    """Endless (batch key, documents) stream: each pass over the pool in
    a fresh seeded order, every batch under a new key."""
    if len(pool) % batch_size:
        raise ValueError(f"pool of {len(pool)} documents is not a whole "
                         f"number of {batch_size}-document batches")
    key, cycle = first_key, 0
    while True:
        order = np.random.RandomState(
            stream_seed(seed, 100 + cycle)).permutation(len(pool))
        for i in range(0, len(pool), batch_size):
            yield key, [pool[j] for j in order[i:i + batch_size]]
            key += 1
        cycle += 1
