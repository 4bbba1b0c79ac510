"""Nougat-base as the configuration's parser model (``harness``'s hooks),
with its plain float32 reference.

The reference is written from the published description (Swin,
arXiv:2103.14030; mBART, arXiv:2001.08210; Nougat, arXiv:2308.13418)
and the values the configuration lists under ``assumed``; it imports
nothing from the program. Every matmul runs at the highest precision:

- encoder, one page at a time: the page drawn from its token ids (the
  rule below), normalised per channel, patches of ``patch`` embedded
  and LayerNormed; per stage, blocks alternately on plain and on
  shifted windows (the grid rolled up and left by half a window), each
  window's attention with the bias table read at each position pair's
  offset and, in a shifted block, -100 between positions whose rows or
  columns did not both wrap (or both stay) in the roll; a GELU (erf)
  MLP; 2 x 2 merging between stages;
- decoder: the full causal forward pass over the fed tokens (no
  cache): scaled token embedding plus learned positions at offset
  ``pos_offset``, LayerNorm, per layer self-attention, cross-attention
  to the encoder's last stage and a GELU (erf) feed-forward, each
  pre-LayerNorm, a final LayerNorm and the tied output head.

The page image (assumed; the corpus has no PDFs): the page's token ids
in reading order, one ``glyph_cell`` each on a white page, its ink the
4 x 4 bits of (id * 2654435761 mod 2^32) >> 16, least significant bit
first, row by row.

Hooks: ``init`` makes the seeded weights on the device in the layout
``repro.models.nougat`` documents; ``backend`` is the program's
``core/parser_model.NougatBackend``; ``warm`` compiles its programs;
``keep`` takes what the timed decode kept of the batch: each page's
decoded steps and, at seeded (page, step) pairs, the logits it
produced and the tokens it fed; ``readings`` compares them with the
reference teacher-forced on the same tokens:

- ``parse_logit_gap``: the largest |program - reference| logit over the
  reference's logit standard deviation at that step;
- ``parse_token_regret``: the reference's best logit less its logit of
  the program's token, on the same scale (a reading, under no limit: a
  sound bf16 decode may take a token whose reference logit is a near
  tie, so its readings overlap the control's);
- ``parse_fed_diff`` (exact): kept steps whose next token, the one the
  decode fed on, is not the argmax of the logits the decode produced
  there;
- ``parse_page_diff`` (exact): pages of the selected documents not
  decoded, and pages decoded that are not theirs;
- ``parse_step_diff`` (exact): pages decoded for another number of
  steps than their token count.

The control (a sample without ``parser``) puts the reference with
every matmul's operands in float8 e4m3 (one scale per tensor) in the
program's place, teacher-forced on the page's own tokens.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXACT = ("parse_fed_diff", "parse_page_diff", "parse_step_diff")
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
MASKED = -100.0
NEG_INF = -1e30
CAPTURE_PAGES, CAPTURE_STEPS = 2, 8


def _widths(widths: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in widths.items()))


def stage_width(w: dict, i: int) -> int:
    return w["embed_dim"] * 2 ** i


def init(widths: dict, seed: int):
    """Seeded weights on the device, in ``param_dtype``: linear weights
    N(0, 1/fan_in), biases N(0, 0.02^2), LayerNorm scales
    1 + N(0, 0.1^2), tables (relative bias, embeddings) N(0, 0.02^2)."""
    w = widths
    spec: dict = {}
    p, c = w["patch"], w["embed_dim"]
    spec["patch"] = {"w": ((p * p * 3, c), p * p * 3), "b": ((c,), "b"),
                     "ln_s": ((c,), "s"), "ln_b": ((c,), "b")}
    spec["stages"] = []
    for i, (depth, heads) in enumerate(zip(w["depths"], w["heads"])):
        c = stage_width(w, i)
        f = w["mlp_ratio"] * c
        blk = {"ln1_s": ((depth, c), "s"), "ln1_b": ((depth, c), "b"),
               "rel": ((depth, (2 * w["window"] - 1) ** 2, heads), "t"),
               "ln2_s": ((depth, c), "s"), "ln2_b": ((depth, c), "b")}
        for name, a, b in (("qkv", c, 3 * c), ("proj", c, c),
                           ("fc1", c, f), ("fc2", f, c)):
            blk[name + "_w"] = ((depth, a, b), a)
            blk[name + "_b"] = ((depth, b), "b")
        stage = {"blocks": blk}
        if i < len(w["depths"]) - 1:
            stage["merge"] = {"ln_s": ((4 * c,), "s"), "ln_b": ((4 * c,), "b"),
                              "w": ((4 * c, 2 * c), 4 * c)}
        spec["stages"].append(stage)
    L, d, f = w["dec_layers"], w["dec_d_model"], w["dec_d_ff"]
    ce = stage_width(w, len(w["depths"]) - 1)
    lay = {}
    for att in ("sa", "ca"):
        for proj in "qkvo":
            a = ce if att == "ca" and proj in "kv" else d
            lay[f"{att}_{proj}_w"] = ((L, a, d), a)
            lay[f"{att}_{proj}_b"] = ((L, d), "b")
    lay.update(fc1_w=((L, d, f), d), fc1_b=((L, f), "b"),
               fc2_w=((L, f, d), f), fc2_b=((L, d), "b"))
    for ln in ("sa", "ca", "ff"):
        lay[f"ln_{ln}_s"], lay[f"ln_{ln}_b"] = ((L, d), "s"), ((L, d), "b")
    spec["dec"] = {"embed": ((w["vocab_size"], d), "t"),
                   "pos": ((w["max_positions"] + w["pos_offset"], d), "t"),
                   "ln_emb_s": ((d,), "s"), "ln_emb_b": ((d,), "b"),
                   "ln_f_s": ((d,), "s"), "ln_f_b": ((d,), "b"),
                   "layers": lay}
    leaves, tree = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    dtype = jnp.dtype(w["param_dtype"])

    def make(key):
        out = []
        for k, (shape, kind) in zip(jax.random.split(key, len(leaves)),
                                    leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            z = (1.0 + 0.1 * z if kind == "s" else 0.02 * z
                 if kind in ("b", "t") else z * kind ** -0.5)
            out.append(z.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(jax.random.key(seed % 2 ** 32))


def backend(widths: dict, weights, name: str):
    from repro.configs.base import NougatConfig
    from repro.core.parser_model import NougatBackend

    cfg = NougatConfig(name="nougat-base", **dict(_widths(widths)))
    return NougatBackend(cfg, weights, name)


def warm(backend) -> None:
    backend.warm()


def keep(backend, row: dict):
    out, backend.last = backend.last, None
    return out


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------


def render(page: np.ndarray, w: dict) -> np.ndarray:
    """The page image, uint8 (H, W), by the rule in the docstring."""
    H, W = w["image_hw"]
    ch, cw = w["glyph_cell"]
    rows, cols = H // ch, W // cw
    img = np.full((H, W), 255, np.uint8)
    for i, tok in enumerate(page[:rows * cols]):
        code = ((int(tok) * 2654435761) % 2 ** 32) >> 16
        r, c = divmod(i, cols)
        for bit in range(16):
            if code >> bit & 1:
                br, bc = divmod(bit, 4)
                y0 = r * ch + br * (ch // 4)
                x0 = c * cw + bc * (cw // 4)
                img[y0:y0 + ch // 4, x0:x0 + cw // 4] = 0
    return img


def _quantize(x):
    """float8 e4m3 with one scale per tensor (the control's precision)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, control):
    q = _quantize if control else (lambda x: x)
    return jnp.einsum(spec, q(a), q(b), precision="highest")


def _ln(x, s, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * s + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def _window_view(x, win):
    """(h, w, C) -> (windows, win*win, C), windows and positions in row
    order."""
    h, w, c = x.shape
    x = x.reshape(h // win, win, w // win, win, c).transpose(0, 2, 1, 3, 4)
    return x.reshape(-1, win * win, c)


def _window_mask(h, w, win, shift):
    """(windows, n, n): 0 where two positions of a window of the rolled
    grid both wrapped or both did not, in rows and in columns; -100
    elsewhere."""
    wrap_r = np.arange(h) >= h - shift
    wrap_c = np.arange(w) >= w - shift
    flags = np.stack(np.broadcast_arrays(wrap_r[:, None], wrap_c[None, :]),
                     -1).astype(np.int32)
    f = _window_view(flags, win)
    same = np.all(f[:, :, None] == f[:, None, :], axis=-1)
    return np.where(same, 0.0, MASKED)


def _rel_bias(table, win):
    """(heads, n, n): the table's row for each position pair's offset."""
    pos = [(r, c) for r in range(win) for c in range(win)]
    idx = np.array([[(r1 - r2 + win - 1) * (2 * win - 1) + (c1 - c2 + win - 1)
                     for r2, c2 in pos] for r1, c1 in pos])
    return table[idx].transpose(2, 0, 1)


@functools.partial(jax.jit, static_argnames=("wt", "control"))
def _encode(params, image, *, wt, control):
    w = dict(wt)
    eps = w["norm_eps"]
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = image.astype(jnp.float32) / 255.0
    x = jnp.stack([(x - m) / s for m, s in zip(MEAN, STD)], -1)
    H, W, _ = x.shape
    k = w["patch"]
    x = x.reshape(H // k, k, W // k, k, 3).transpose(0, 2, 1, 3, 4)
    x = x.reshape(H // k, W // k, k * k * 3)
    x = _mm("hwi,io->hwo", x, p["patch"]["w"], control) + p["patch"]["b"]
    x = _ln(x, p["patch"]["ln_s"], p["patch"]["ln_b"], eps)
    win = w["window"]
    for i, (depth, heads) in enumerate(zip(w["depths"], w["heads"])):
        sp = p["stages"][i]
        for j in range(depth):
            bp = jax.tree_util.tree_map(lambda a: a[j], sp["blocks"])
            x = _swin_block(x, bp, heads, win, win // 2 if j % 2 else 0,
                            eps, control)
        if "merge" in sp:
            m = sp["merge"]
            x = jnp.concatenate([x[0::2, 0::2], x[1::2, 0::2],
                                 x[0::2, 1::2], x[1::2, 1::2]], -1)
            x = _mm("hwi,io->hwo", _ln(x, m["ln_s"], m["ln_b"], eps), m["w"],
                    control)
    return x.reshape(-1, x.shape[-1])


def _swin_block(x, bp, heads, win, shift, eps, control):
    h, w, c = x.shape
    dh = c // heads
    y = _ln(x, bp["ln1_s"], bp["ln1_b"], eps)
    y = jnp.roll(y, (-shift, -shift), axis=(0, 1))
    qkv = _mm("wni,io->wno", _window_view(y, win), bp["qkv_w"], control) \
        + bp["qkv_b"]
    q, kk, v = (qkv[..., i * c:(i + 1) * c].reshape(*qkv.shape[:2], heads, dh)
                for i in range(3))
    s = _mm("wnhd,wmhd->whnm", q, kk, control) / math.sqrt(dh)
    s = s + _rel_bias(bp["rel"], win)[None]
    if shift:
        s = s + _window_mask(h, w, win, shift)[:, None]
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("whnm,wmhd->wnhd", a, v, control).reshape(-1, win * win, c)
    o = _mm("wni,io->wno", o, bp["proj_w"], control) + bp["proj_b"]
    o = o.reshape(h // win, w // win, win, win, c).transpose(0, 2, 1, 3, 4)
    x = x + jnp.roll(o.reshape(h, w, c), (shift, shift), axis=(0, 1))
    y = _ln(x, bp["ln2_s"], bp["ln2_b"], eps)
    y = _gelu(_mm("hwi,io->hwo", y, bp["fc1_w"], control) + bp["fc1_b"])
    return x + _mm("hwi,io->hwo", y, bp["fc2_w"], control) + bp["fc2_b"]


@functools.partial(jax.jit, static_argnames=("wt", "control"))
def _decode(params, memory, tokens, *, wt, control):
    """(T,) fed tokens -> (T, vocab) logits, causal over T."""
    w = dict(wt)
    eps = w["norm_eps"]
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               params["dec"])
    t = tokens.shape[0]
    d, nh = w["dec_d_model"], w["dec_heads"]
    dh = d // nh
    x = p["embed"][tokens] * (math.sqrt(d) if w["scale_embedding"] else 1.0)
    x = _ln(x + p["pos"][jnp.arange(t) + w["pos_offset"]], p["ln_emb_s"],
            p["ln_emb_b"], eps)
    causal = np.where(np.arange(t)[None] <= np.arange(t)[:, None], 0.0,
                      NEG_INF)

    def attend(y, kv, pre, lp, bias):
        q = (_mm("ti,io->to", y, lp[pre + "_q_w"], control)
             + lp[pre + "_q_b"]).reshape(-1, nh, dh)
        k = (_mm("ti,io->to", kv, lp[pre + "_k_w"], control)
             + lp[pre + "_k_b"]).reshape(-1, nh, dh)
        v = (_mm("ti,io->to", kv, lp[pre + "_v_w"], control)
             + lp[pre + "_v_b"]).reshape(-1, nh, dh)
        s = _mm("qhd,khd->hqk", q / math.sqrt(dh), k, control) + bias
        o = _mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v, control)
        return _mm("ti,io->to", o.reshape(-1, d), lp[pre + "_o_w"],
                   control) + lp[pre + "_o_b"]

    for l in range(w["dec_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[l], p["layers"])
        y = _ln(x, lp["ln_sa_s"], lp["ln_sa_b"], eps)
        x = x + attend(y, y, "sa", lp, causal)
        y = _ln(x, lp["ln_ca_s"], lp["ln_ca_b"], eps)
        x = x + attend(y, memory, "ca", lp, 0.0)
        y = _ln(x, lp["ln_ff_s"], lp["ln_ff_b"], eps)
        y = _gelu(_mm("ti,io->to", y, lp["fc1_w"], control) + lp["fc1_b"])
        x = x + _mm("ti,io->to", y, lp["fc2_w"], control) + lp["fc2_b"]
    x = _ln(x, p["ln_f_s"], p["ln_f_b"], eps)
    return _mm("td,vd->tv", x, p["embed"], control)


def reference_logits(weights, widths: dict, page: np.ndarray,
                     fed: np.ndarray, steps, control: bool = False):
    """(len(steps), vocab) float64 logits of the page at ``steps``, the
    decoder teacher-forced on ``fed`` (padded to ``cache_len``)."""
    wt = _widths(widths)
    memory = _encode(weights, jnp.asarray(render(page, widths)), wt=wt,
                     control=control)
    tokens = np.zeros(widths["cache_len"], np.int32)
    tokens[:len(fed)] = fed
    out = _decode(weights, memory, jnp.asarray(tokens), wt=wt,
                  control=control)
    return np.asarray(out, np.float64)[np.asarray(steps)]


def _pairs(rng, lengths):
    """The control's (page, steps) pairs: 2 pages, 8 steps each with the
    first and the last."""
    pages = np.flatnonzero(np.asarray(lengths) > 0)
    chosen = np.sort(rng.choice(pages, min(CAPTURE_PAGES, len(pages)),
                                replace=False)) if len(pages) else []
    out = []
    for p in chosen:
        n = int(lengths[p])
        inner = rng.choice(np.arange(1, max(n - 1, 1)),
                           min(CAPTURE_STEPS - 2, max(n - 2, 0)),
                           replace=False)
        out.append((int(p), sorted({0, n - 1, *map(int, inner)})))
    return out


def readings(sample: dict, weights, widths: dict) -> dict:
    docs = [sample["docs"][i] for i in sample["selected"]]
    pages = {(d.doc_id, j): p for d in docs for j, p in enumerate(d.pages)}
    got = sample.get("parser")
    if got is not None:
        keys = [tuple(k) for k in got["pages"]]
        page_diff = len(set(keys) ^ set(pages))
        step_diff = sum(k not in pages or len(pages[k]) != s
                        for k, s in zip(keys, got["steps"]))
        fed_diff = sum(int(np.sum(np.argmax(c["logits"], -1)
                                  != c["tokens"][np.asarray(c["steps"]) + 1]))
                       for c in got["captures"])
        # (page, steps, fed tokens, the program's token at each step,
        # its logits there)
        caps = [(tuple(c["page"]), c["steps"], c["tokens"][:-1],
                 c["tokens"][np.asarray(c["steps"]) + 1], c["logits"])
                for c in got["captures"]]
    else:
        page_diff = step_diff = fed_diff = 0
        keys = list(pages)
        rng = np.random.RandomState(int(sample["key"]) % 2 ** 32)
        caps = []
        for p, steps in _pairs(rng, [len(pages[k]) for k in keys]):
            page = pages[keys[p]]
            fed = np.concatenate([[widths["bos_id"]],
                                  page[:-1] % widths["vocab_size"]])
            lg = reference_logits(weights, widths, page, fed, steps,
                                  control=True)
            caps.append((keys[p], steps, fed, np.argmax(lg, -1), lg))
    gap = regret = 0.0
    for key, steps, fed, tok, logits in caps:
        if key not in pages:
            continue
        ref = reference_logits(weights, widths, pages[key], fed, steps)
        scale = ref.std(axis=-1)
        gap = max(gap, float(np.max(np.abs(np.asarray(logits) - ref).max(-1)
                                    / scale)))
        chosen = np.take_along_axis(ref, np.asarray(tok)[:, None], -1)[:, 0]
        regret = max(regret, float(np.max((ref.max(-1) - chosen) / scale)))
    return {"parse_logit_gap": gap, "parse_token_regret": regret,
            "parse_fed_diff": fed_diff, "parse_page_diff": page_diff,
            "parse_step_diff": step_diff}
