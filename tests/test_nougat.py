"""Nougat at its tiny preset on the CPU: the served decode (encoder into
slot caches, greedy steps through the self-attention cache) against
the benchmark's plain float32 reference, the Swin window mask and
relative-position index against their brute-force forms, and the
device backend inside the engine."""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends as B
from repro.core import obs
from repro.core import parser_model as PM
from repro.core.engine import AdaParseEngine, EngineConfig
from repro.models import nougat

REFERENCE = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
             / "chip" / "configs" / "nougat_parser.py")
#: float32 on both sides, the same operations in another order (slot
#: batching, cached keys, fused softmax): the CPU reads about 1e-6 of
#: the reference's logit spread; a wrong mask, bias or position reads
#: above 1e-2
TOLERANCE = 1e-4


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("nougat_parser", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(ref):
    """The tiny preset, its widths as a configuration file gives them,
    and weights from the reference's seeded init."""
    cfg = PM.parser_config("reduced")
    widths = {f: getattr(cfg, f) for f in cfg.__dataclass_fields__
              if f != "name"}
    return cfg, widths, ref.init(widths, 2 ** 31 + 5)


def _pages(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(10, 31090, size=n).astype(np.int32)
            for n in lengths]


def test_reference_init_matches_the_program_layout(tiny):
    cfg, _, weights = tiny
    mine = nougat.init_params(cfg, 7)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(weights)
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(mine)] \
        == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(weights)]


@pytest.mark.parametrize("lengths", [(20, 37, 5, 64, 130, 1),
                                     tuple(range(40, 60))],
                         ids=["one_wave", "two_waves"])
def test_decode_through_the_cache_matches_the_reference(tiny, ref,
                                                        monkeypatch,
                                                        lengths):
    """Every step of two pages (all their steps captured), the decode's
    logits against the reference's full forward pass teacher-forced on
    the tokens the decode fed; each page decoded for exactly its token
    count. Every wave encodes both chunks of the 16 slots, blank pages
    in the slots it leaves empty; the first case reads two cache
    blocks, the second takes two waves."""
    cfg, widths, weights = tiny
    monkeypatch.setattr(PM, "CAPTURE_STEPS", 200)
    be = PM.NougatBackend(cfg, weights)
    pages = _pages(1, lengths)
    out = be.run([(0, j) for j in range(len(pages))], pages,
                 np.random.RandomState(3))
    assert out["steps"] == list(lengths)
    assert len(out["captures"]) == 2
    for cap in out["captures"]:
        page = pages[cap["page"][1]]
        assert cap["steps"] == list(range(len(page)))
        want = ref.reference_logits(weights, widths, page,
                                    cap["tokens"][:-1], cap["steps"])
        gap = np.abs(cap["logits"] - want).max(-1) / want.std(-1)
        assert gap.max() < TOLERANCE
        # greedy: each fed token is the argmax of the step before
        np.testing.assert_array_equal(cap["tokens"][1:],
                                      np.argmax(cap["logits"], -1))
        assert cap["tokens"][0] == cfg.bos_id


def test_attention_kernel_decodes_as_the_einsum_path(ref):
    """``NougatBackend`` with the decode attention kernel forced
    (interpret mode), on the tiny preset widened to rows of whole
    128-lane tiles (the kernel's shape rule), against the same backend
    on the einsum path: the same steps, fed tokens and captured logits;
    ``parse.attention_kernel`` counts every decode step of the kernel
    path and none of the other. Two waves, the first reading two cache
    blocks, with slots that finish early or hold no page."""
    import dataclasses

    cfg = dataclasses.replace(PM.parser_config("reduced"), dec_d_model=128)
    widths = {f: getattr(cfg, f) for f in cfg.__dataclass_fields__
              if f != "name"}
    weights = ref.init(widths, 2 ** 31 + 11)
    pages = _pages(8, [130, 3, 41, 9] + [12] * 14)
    keys = [(0, j) for j in range(len(pages))]
    runs = []
    for force in (False, True):
        be = PM.NougatBackend(cfg, weights, force_kernel=force)
        assert be.attention_kernel == force
        before = obs.metrics().snapshot()["counters"]
        out = be.run(keys, pages, np.random.RandomState(4))
        after = obs.metrics().snapshot()["counters"]
        grew = {k: after.get(k, 0) - before.get(k, 0)
                for k in (obs.PARSE_ATTENTION_KERNEL, "parse.decode_steps")}
        assert grew["parse.decode_steps"] == 130 + 12
        assert grew[obs.PARSE_ATTENTION_KERNEL] == \
            (grew["parse.decode_steps"] if force else 0)
        runs.append(out)
    einsum, kernel = runs
    assert kernel["steps"] == einsum["steps"] == [len(p) for p in pages]
    assert len(kernel["captures"]) == len(einsum["captures"]) == 2
    for a, b in zip(kernel["captures"], einsum["captures"]):
        assert a["page"] == b["page"] and a["steps"] == b["steps"]
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        gap = np.abs(a["logits"] - b["logits"]).max(-1) / b["logits"].std(-1)
        assert gap.max() < TOLERANCE


def test_every_wave_encodes_every_slot(tiny):
    """A wave runs the encoder over all its slots whatever the number of
    pages it holds, and a slot no page holds gets a blank page's
    cross-attention K/V, not what the wave before left there."""
    cfg, _, weights = tiny
    be = PM.NougatBackend(cfg, weights)
    calls = []
    encode = be.encode

    def counted(*args):
        calls.append(args[1].shape[0])
        return encode(*args)
    be.encode = counted
    full = _pages(5, [12] * cfg.decode_slots)
    be.run([(0, j) for j in range(len(full))], full,
           np.random.RandomState(0))
    assert sum(calls) == cfg.decode_slots
    calls.clear()
    be.run([(1, 0)], _pages(6, [9]), np.random.RandomState(0))
    assert calls == [nougat.ENCODE_CHUNK] * (cfg.decode_slots
                                             // nougat.ENCODE_CHUNK)
    # float32 both ways; a chunk of eight and a lone page may round
    # apart in the last bits, a stale slot differs at order one
    blank = np.full((1,) + tuple(cfg.image_hw), 255, np.uint8)
    k, v = nougat.cross_kv(weights, cfg, nougat.encode(weights, cfg, blank))
    for layer in range(cfg.dec_layers):
        np.testing.assert_allclose(np.asarray(be.caches["xk"][layer][1:]),
                                   np.broadcast_to(
                                       np.asarray(k[layer]),
                                       be.caches["xk"][layer][1:].shape),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(be.caches["xv"][layer][-1]),
                                   np.asarray(v[layer][0]),
                                   rtol=1e-5, atol=1e-5)


def test_teacher_forced_forward_matches_the_reference(tiny, ref):
    cfg, widths, weights = tiny
    page = _pages(2, [30])[0]
    fed = np.concatenate([[cfg.bos_id], page[:-1] % cfg.vocab_size])
    got = np.asarray(nougat.forward(
        weights, cfg, jnp.asarray(nougat.page_images([page], cfg)),
        jnp.asarray(fed[None])))[0]
    want = ref.reference_logits(weights, widths, page, fed, range(30))
    assert (np.abs(got - want).max(-1) / want.std(-1)).max() < TOLERANCE


def test_page_image_follows_the_reference_rule(tiny, ref):
    cfg, widths, _ = tiny
    pages = _pages(4, [0, 3, 64, 90])
    got = nougat.page_images(pages, cfg)
    for img, page in zip(got, pages):
        np.testing.assert_array_equal(img, ref.render(page, widths))


def test_control_reads_far_above_the_program(tiny, ref):
    """The float8 reference in the program's place reads a gap hundreds
    of times the tolerance the program is held to."""
    from repro.data.synthetic import Document

    cfg, widths, weights = tiny
    docs = [Document(i, _pages(10 + i, [25, 33]), 0.5, 0.1, "pdflatex",
                     "ArXiv", "cs", 2020, False) for i in range(3)]
    r = ref.readings({"docs": docs, "selected": np.array([0, 2]),
                      "key": 11}, weights, widths)
    assert r["parse_logit_gap"] > 100 * TOLERANCE
    assert r["parse_page_diff"] == r["parse_step_diff"] == 0
    assert r["parse_fed_diff"] == 0


@pytest.mark.parametrize("h,w,window", [(16, 24, 4), (8, 12, 4),
                                        (224, 168, 7), (28, 21, 7)])
def test_shift_mask_is_same_region_after_the_roll(h, w, window):
    """Two positions of one window of the grid rolled by half a window
    may attend iff each lies in the same region of the unrolled grid:
    both or neither among the rows (and the columns) the roll wrapped."""
    shift = window // 2
    mask = nougat.shift_mask(h, w, window, shift)
    rows = np.roll(np.arange(h), -shift)
    cols = np.roll(np.arange(w), -shift)
    n = window * window
    k = 0
    for wr in range(0, h, window):
        for wc in range(0, w, window):
            cells = [(rows[wr + i // window], cols[wc + i % window])
                     for i in range(n)]
            for a in range(n):
                for b in range(n):
                    (ra, ca), (rb, cb) = cells[a], cells[b]
                    same = ((ra < shift) == (rb < shift)
                            and (ca < shift) == (cb < shift))
                    assert mask[k, a, b] == (0.0 if same else nougat.MASKED)
            k += 1
    assert k == mask.shape[0]


@pytest.mark.parametrize("window", [4, 7])
def test_relative_position_index_is_its_formula(window):
    idx = nougat.relative_position_index(window)
    n = window * window
    for a in range(n):
        for b in range(n):
            (ra, ca), (rb, cb) = divmod(a, window), divmod(b, window)
            assert idx[a, b] == ((ra - rb + window - 1) * (2 * window - 1)
                                 + ca - cb + window - 1)
    assert idx.min() == 0 and idx.max() == (2 * window - 1) ** 2 - 1


def test_engine_records_equal_the_channel_backends(corpus, ft_router):
    """An engine whose ``nougat`` is the device backend emits exactly the
    channel backend's records, and decodes exactly the selected
    documents' pages, each for its token count."""
    ccfg, docs = corpus
    ecfg = EngineConfig(alpha=0.1, batch_size=32, seed=3)
    test = docs[75:139]
    want = AdaParseEngine(ecfg, ft_router, ccfg).run(test)
    channel = B.get_backend("nougat")
    be = PM.NougatBackend.seeded("reduced", 9)
    decoded = []
    parse = be.parse_batch

    def parse_batch(sel, *a, **kw):
        out = parse(sel, *a, **kw)
        decoded.append((sel, be.last))
        return out
    be.parse_batch = parse_batch
    before = obs.metrics().snapshot()["counters"]
    B.register_backend(be, overwrite=True)
    try:
        got = AdaParseEngine(ecfg, ft_router, ccfg).run(test)
    finally:
        B.register_backend(channel, overwrite=True)
    assert set(got) == set(want)
    for i in want:
        assert got[i].parser == want[i].parser
        assert len(got[i].pages) == len(want[i].pages)
        for a, b in zip(got[i].pages, want[i].pages):
            np.testing.assert_array_equal(a, b)
    assert len(decoded) == 2
    for sel, last in decoded:
        assert len(sel) == 3
        assert last["pages"] == [(d.doc_id, j) for d in sel
                                 for j in range(d.n_pages)]
        assert last["steps"] == last["lengths"] == [len(p) for d in sel
                                                    for p in d.pages]
    after = obs.metrics().snapshot()["counters"]
    lengths = np.concatenate([last["lengths"] for _, last in decoded])
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in obs.PARSE_COUNTERS}
    assert grew["parse.pages"] == len(lengths)
    assert grew["parse.live_slot_steps"] == lengths.sum()
    assert grew["parse.live_kv_positions"] == (lengths * (lengths + 1)
                                               // 2).sum()
    assert grew["parse.slot_steps"] == \
        be.cfg.decode_slots * grew["parse.decode_steps"]
