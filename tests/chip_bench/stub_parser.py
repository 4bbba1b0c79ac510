"""A parser model small enough for the CPU tests, written to the hooks
``harness`` documents: its backend parses with the ``nougat`` channel
(so the records stay the channel's) and runs one jitted op per batch,
``tanh(x @ w)`` over a fixed number of rows, on each selected document's
first ``d`` first-page tokens. Its reference is the same op in float64
numpy; its control, the op in float16.

Widths: ``d`` (features and the square weight's side), ``rows`` (the
op's fixed row count, at least floor(alpha*k))."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: numbers summed over the sampled batches
EXACT = ("stub_docs_diff",)
#: every hook called, in order, by the module loaded for one run
CALLS: list[str] = []


def init(widths: dict, seed: int):
    """The (d, d) weight, on the device."""
    CALLS.append("init")
    d = widths["d"]
    return jax.jit(lambda k: jax.random.normal(k, (d, d), jnp.float32)
                   * d ** -0.5)(jax.random.key(seed))


def features(docs, widths: dict) -> np.ndarray:
    """(rows, d): each document's first ``d`` first-page tokens over
    1e4, zero-padded; rows past the documents are zero."""
    x = np.zeros((widths["rows"], widths["d"]), np.float64)
    for i, doc in enumerate(docs):
        page = doc.pages[0][:widths["d"]] if doc.pages else []
        x[i, :len(page)] = np.asarray(page, np.float64) / 1e4
    return x


class StubBackend:
    """The ``nougat`` channel's pages, and the op on the selected
    documents, its output kept for the batch's check."""

    def __init__(self, channel, widths: dict, weights):
        self.channel, self.widths, self.weights = channel, widths, weights
        self.info = dataclasses.replace(channel.info)
        self.op = jax.jit(lambda w, x: jnp.tanh(x @ w))
        self.last = None

    def run(self, docs):
        x = jnp.asarray(features(docs, self.widths), jnp.float32)
        return np.asarray(self.op(self.weights, x))

    def parse_batch(self, docs, cfg, rng, **kw):
        pages = self.channel.parse_batch(docs, cfg, rng, **kw)
        self.last = ([d.doc_id for d in docs], self.run(docs))
        return pages

    def cost_batch(self, docs):
        return self.channel.cost_batch(docs)


def backend(widths: dict, weights, name: str):
    from repro.core import backends

    CALLS.append("backend")
    return StubBackend(backends.get_backend(name), widths, weights)


def warm(backend: StubBackend) -> None:
    CALLS.append("warm")
    backend.run([])


def keep(backend: StubBackend, row: dict):
    if "keep" not in CALLS:
        CALLS.append("keep")
    out, backend.last = backend.last, None
    return out


def readings(sample: dict, weights, widths: dict) -> dict:
    """The op's widest gap from the float64 reference, and the selected
    documents it did not run on. A sample without ``parser`` is the
    control's: the op in float16 stands in for the program."""
    if "readings" not in CALLS:
        CALLS.append("readings")
    docs = [sample["docs"][i] for i in sample["selected"]]
    w = np.asarray(weights, np.float64)
    want = np.tanh(features(docs, widths) @ w)
    if "parser" in sample:
        ids, got = sample["parser"]
    else:
        ids = [d.doc_id for d in docs]
        x16 = features(docs, widths).astype(np.float16)
        got = np.tanh(x16 @ w.astype(np.float16)).astype(np.float64)
    return {"stub_gap": float(np.max(np.abs(got - want))),
            "stub_docs_diff": len(set(ids) ^ {d.doc_id for d in docs})}
