"""The expensive parser as a device model: Nougat (``models/nougat``)
behind the ``ParserBackend`` interface, so that ``engine.complete_batch``
runs it unchanged, inside its ``reparse`` span.

One ``parse_batch``:

1. The records' text is the ``nougat`` corruption channel's pages, drawn
   first from the batch's stream exactly as the channel backend draws
   them: seeded weights cannot write real text, so the model supplies
   the time and the logits, and the channel the text.
2. Every page of every selected document is drawn as an image
   (``models.nougat.page_images``; span ``parse.render``).
3. In waves of ``decode_slots`` pages (one wave where the batch's pages
   fit): the encoder runs over every slot, in chunks of
   ``nougat.ENCODE_CHUNK`` pages, a blank page in each slot no page
   holds, and writes each slot's cross-attention K/V
   (``parse.encode``); then every slot decodes greedily from BOS at
   once, each page for exactly its own token count, the cache read a
   ``nougat.KV_BLOCK`` at a time (``parse.decode``). Both end blocked
   on the device (``parse.wait``). So a wave's device time does not
   depend on how many pages it holds: it lasts as long as its longest
   page, and slots whose page has finished, or that hold none, still
   run.
4. ``last`` keeps what the batch decoded, for a check: each page's
   decoded step count, and at seeded (page, step) pairs (2 pages, 8
   steps each, among them each page's first and last) the logits the
   decode produced and the tokens it fed.

Counters (``obs.PARSE_COUNTERS``) count the decode's pages, steps, slot
steps, live slot steps and live cache positions;
``obs.PARSE_ATTENTION_KERNEL`` the steps whose attention ran
``kernels.decode_attention``, which reads live slots' K/V only (a TPU,
or ``force_kernel``).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core import backends, obs
from repro.models import nougat

#: pages and steps per page whose logits a batch keeps
CAPTURE_PAGES = 2
CAPTURE_STEPS = 8
PARSER_CONFIGS = ("reduced", "published")


def parser_config(which: str = "reduced"):
    """The ``nougat-base`` model at its published widths, or its tiny
    preset."""
    from repro.configs import get_config

    if which not in PARSER_CONFIGS:
        raise ValueError(f"parser config {which!r} not in {PARSER_CONFIGS}")
    arch = get_config("nougat-base")
    return arch.model if which == "published" else arch.reduced().model


class NougatBackend:
    """``ParserBackend`` running Nougat on the default device, under the
    name (and the text) of the channel backend it stands in for."""

    def __init__(self, cfg, params, name: str = "nougat", *,
                 force_kernel: bool = False):
        if cfg.decode_slots % nougat.ENCODE_CHUNK:
            raise ValueError(f"{cfg.decode_slots} decode slots do not hold "
                             f"whole encode chunks of {nougat.ENCODE_CHUNK}")
        if (cfg.cache_len % nougat.KV_BLOCK
                or cfg.cache_len > cfg.max_positions):
            raise ValueError(f"cache of {cfg.cache_len} positions is not "
                             f"whole blocks of {nougat.KV_BLOCK} within the "
                             f"{cfg.max_positions} learned positions")
        self.cfg, self.params = cfg, params
        self.channel = backends.get_backend(name)
        self.info = dataclasses.replace(self.channel.info)
        self.encode, self.decode = nougat.serving_programs(cfg,
                                                           force_kernel)
        self.attention_kernel = nougat.uses_attention_kernel(cfg,
                                                             force_kernel)
        self.caches = nougat.slot_caches(cfg)
        self.last: dict | None = None

    @classmethod
    def seeded(cls, which: str = "reduced", seed: int = 0,
               name: str = "nougat") -> "NougatBackend":
        cfg = parser_config(which)
        return cls(cfg, nougat.init_params(cfg, seed), name)

    def cost_batch(self, docs):
        return self.channel.cost_batch(docs)

    def parse_batch(self, docs, cfg, rng, *, image_degraded=False,
                    text_degraded=False):
        text = self.channel.parse_batch(docs, cfg, rng,
                                        image_degraded=image_degraded,
                                        text_degraded=text_degraded)
        keys = [(d.doc_id, j) for d in docs for j in range(d.n_pages)]
        pages = [p for d in docs for p in d.pages]
        self.last = self.run(keys, pages, rng)
        return text

    def run(self, keys: list, pages: list, rng) -> dict:
        """Decode ``pages`` (token-id arrays, named by ``keys``); the
        capture pairs are drawn from ``rng``."""
        lengths = np.array([len(p) for p in pages], np.int64)
        if len(lengths) and lengths.max() > self.cfg.cache_len:
            raise ValueError(f"a page of {lengths.max()} tokens is longer "
                             f"than the {self.cfg.cache_len}-position cache")
        captures = capture_pairs(rng, lengths)
        out = {"pages": list(keys), "lengths": lengths.tolist(),
               "steps": [0] * len(pages), "captures": []}
        slots = self.cfg.decode_slots
        for w0 in range(0, len(pages), slots):
            wave = range(w0, min(w0 + slots, len(pages)))
            caps = [(p, s) for p, s in captures if p in wave]
            steps, tokens, logits = self._wave([pages[p] for p in wave],
                                               lengths[w0:wave.stop],
                                               [(p - w0, s) for p, s in caps])
            out["steps"][w0:wave.stop] = steps[:len(wave)].tolist()
            for (p, s), lg in zip(caps, _split(logits, caps)):
                out["captures"].append({
                    "page": keys[p], "steps": s, "logits": lg,
                    "tokens": tokens[p - w0, :s[-1] + 2].copy()})
        return out

    def _wave(self, pages: list, lengths: np.ndarray, caps: list):
        cfg = self.cfg
        slots, chunk = cfg.decode_slots, nougat.ENCODE_CHUNK
        with obs.span("parse.render", None):
            images = nougat.page_images(pages, cfg)
            images = np.concatenate([images, np.full(
                (slots - len(images),) + images.shape[1:], 255, np.uint8)])
        c = self.caches
        with obs.span("parse.encode", None):
            for i in range(0, len(images), chunk):
                c["xk"], c["xv"] = self.encode(self.params,
                                               images[i:i + chunk], c["xk"],
                                               c["xv"], np.int32(i))
            with obs.span("parse.wait", None):
                jax.block_until_ready(c["xv"])
        n = np.zeros(slots, np.int32)
        n[:len(lengths)] = lengths
        t_max = int(n.max())
        with obs.span("parse.decode", None):
            state = self._state(n, caps)
            for t in range(t_max):
                state = self.decode(self.params, state, c["xk"], c["xv"],
                                    np.int32(t), span=_span(t))
            c["ck"], c["cv"] = state["ck"], state["cv"]
            with obs.span("parse.wait", None):
                steps, tokens, logits = jax.device_get(
                    (state["steps"], state["tokens"], state["cap"]))
        counts = (len(lengths), t_max, slots * t_max, int(lengths.sum()),
                  int((lengths * (lengths + 1) // 2).sum()))
        for name, n in zip(obs.PARSE_COUNTERS, counts):
            obs.metrics().count(name, n)
        if self.attention_kernel:
            obs.metrics().count(obs.PARSE_ATTENTION_KERNEL, t_max)
        return steps, tokens, logits

    def _state(self, n: np.ndarray, caps: list) -> dict:
        """A wave's decode state: ``n`` steps per slot, the capture pairs
        ``caps`` of (slot, steps)."""
        k = CAPTURE_PAGES * CAPTURE_STEPS
        cap_slot = np.zeros(k, np.int32)
        cap_step = np.full(k, -1, np.int32)
        flat = [(p, t) for p, s in caps for t in s][:k]
        for j, (p, t) in enumerate(flat):
            cap_slot[j], cap_step[j] = p, t
        return nougat.decode_state(self.cfg, self.caches, n, cap_slot,
                                   cap_step)

    def warm(self) -> None:
        """Compile (or load) the encode chunk and every decode block's
        program, with no step run."""
        cfg = self.cfg
        c = self.caches
        blank = np.full((nougat.ENCODE_CHUNK,) + tuple(cfg.image_hw), 255,
                        np.uint8)
        c["xk"], c["xv"] = self.encode(self.params, blank, c["xk"], c["xv"],
                                       np.int32(0))
        state = self._state(np.zeros(cfg.decode_slots, np.int32), [])
        for t in range(0, cfg.cache_len, nougat.KV_BLOCK):
            state = self.decode(self.params, state, c["xk"], c["xv"],
                                np.int32(t), span=_span(t))
        c["ck"], c["cv"] = jax.block_until_ready((state["ck"], state["cv"]))


def capture_pairs(rng, lengths: np.ndarray) -> list:
    """Seeded (page, steps) pairs: ``CAPTURE_PAGES`` pages with at least
    one token, and of each ``CAPTURE_STEPS`` distinct steps (fewer on a
    shorter page), its first and last among them, in order."""
    pages = np.flatnonzero(np.asarray(lengths) > 0)
    chosen = np.sort(rng.choice(pages, size=min(CAPTURE_PAGES, len(pages)),
                                replace=False)) if len(pages) else []
    out = []
    for p in chosen:
        n = int(lengths[p])
        inner = rng.choice(np.arange(1, max(n - 1, 1)),
                           size=min(CAPTURE_STEPS - 2, max(n - 2, 0)),
                           replace=False)
        out.append((int(p), sorted({0, n - 1, *map(int, inner)})))
    return out


def _span(t: int) -> int:
    """Cache positions a step at position ``t`` reads: whole blocks."""
    return (t // nougat.KV_BLOCK + 1) * nougat.KV_BLOCK


def _split(logits: np.ndarray, caps: list) -> list:
    """The rows of ``logits`` that belong to each capture, in order."""
    out, i = [], 0
    for _, s in caps:
        out.append(logits[i:i + len(s)].copy())
        i += len(s)
    return out


def register(which: str, seed: int = 0,
             name: str = "nougat") -> NougatBackend:
    """Put a seeded Nougat backend under ``name`` in the registry, in
    place of the channel backend there."""
    return backends.register_backend(NougatBackend.seeded(which, seed, name),
                                     overwrite=True)
