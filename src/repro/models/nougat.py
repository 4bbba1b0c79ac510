"""Nougat (Blecher et al., arXiv:2308.13418): a Swin encoder over the page
image and an mBART decoder over the page's text, as the program serves
them.

Encoder (Swin, arXiv:2103.14030): the page, normalised per channel, is
cut into ``patch`` x ``patch`` patches, each embedded by a linear map and
a LayerNorm. Each stage runs pairs of blocks, the second of each pair
on windows shifted by half a window (a roll of the grid, with the
wrapped regions masked apart); a block is pre-LayerNorm window
attention with a learned relative-position bias, then a pre-LayerNorm
GELU (erf) MLP of ``mlp_ratio`` x. Between stages, 2 x 2 patch merging
(concatenate, LayerNorm, linear 4C -> 2C). The last stage's tokens are
the memory the decoder attends to; no norm follows it.

Decoder (mBART, arXiv:2001.08210): token embeddings (times sqrt(d) with
``scale_embedding``) plus learned positions (offset ``pos_offset``),
a LayerNorm, then per layer pre-LayerNorm causal self-attention,
cross-attention to the memory and a GELU (erf) feed-forward, a final
LayerNorm and an output head tied to the token embedding. Every
projection has a bias; queries are scaled by head_dim^-0.5.

Precision: parameters and activations in ``compute_dtype``; attention
scores, softmax and LayerNorm statistics in float32.

Serving splits the decoder in two programs, named so that a profiler
trace shows them: ``parse_encode`` (a chunk of pages through the
encoder into every layer's cross-attention K/V, written into page
slots) and ``parse_decode`` (one greedy step for every slot at once
over a self-attention cache kept in blocks of ``KV_BLOCK`` positions;
one program per block count, so a step reads the cache only up to the
block its position lies in). On a TPU the step's attention is
``kernels.decode_attention``, which reads the K/V of live slots only
(rows of whole 128-lane tiles; ``_attend_rows`` otherwise).
``core/parser_model`` drives them.

Weights (``init_params``) are plain nested dicts; linear weights are
(in, out):
- ``patch``: ``w`` (patch*patch*3, C) over a patch flattened by (row,
  column, channel), ``b``, ``ln_s``, ``ln_b``;
- ``stages[i]["blocks"]``, stacked over the stage's depth: ``ln1_s``,
  ``ln1_b``, ``qkv_w`` (C, 3C: q, k, v, each by head), ``qkv_b``,
  ``rel`` ((2w-1)^2, heads), ``proj_w``, ``proj_b``, ``ln2_s``,
  ``ln2_b``, ``fc1_w``, ``fc1_b``, ``fc2_w``, ``fc2_b``;
  ``stages[i]["merge"]`` (all but the last): ``ln_s``, ``ln_b`` (4C),
  ``w`` (4C, 2C);
- ``dec``: ``embed`` (vocab, d), ``pos`` (max_positions + offset, d),
  ``ln_emb_s``, ``ln_emb_b``, ``ln_f_s``, ``ln_f_b`` and ``layers``,
  stacked over the layers: ``sa_{q,k,v,o}_{w,b}``, ``ca_{q,k,v,o}_{w,b}``
  (``ca_k_w``, ``ca_v_w`` from the encoder's width), ``fc1_w``,
  ``fc1_b``, ``fc2_w``, ``fc2_b``, ``ln_sa_*``, ``ln_ca_*``, ``ln_ff_*``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import NougatConfig
from repro.kernels import decode_attention
from repro.models.layers import gelu, layer_norm

#: Nougat's input normalisation (ImageNet's per-channel mean and std)
PIXEL_MEAN = (0.485, 0.456, 0.406)
PIXEL_STD = (0.229, 0.224, 0.225)
#: Swin's additive mask between the wrapped regions of a shifted window
MASKED = -100.0
NEG_INF = -1e30
#: serving: cache positions per self-attention block (one decode program
#: per block count) and pages per encoder call
KV_BLOCK = 128
ENCODE_CHUNK = 8
#: cross-attention K/V rows are kept in whole (16, 128) bf16 tiles: a
#: (slots, 588, d) cache is laid out position by position (588 rows are
#: not whole tiles), which interleaves the slots; padded, slot by slot,
#: so a slot's K/V is one contiguous read. The padding rows are masked.
CROSS_ROWS = 16


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def param_shapes(cfg: NougatConfig) -> dict:
    """Leaf -> (shape, kind): ``("w", fan_in)`` for a linear weight (std
    fan_in^-0.5), ``"b"`` a bias, ``"s"`` a LayerNorm scale, ``"t"`` a
    table (std 0.02)."""
    p = cfg.patch
    c = cfg.embed_dim
    out = {"patch": {"w": ((p * p * 3, c), ("w", p * p * 3)),
                     "b": ((c,), "b"), "ln_s": ((c,), "s"),
                     "ln_b": ((c,), "b")}, "stages": []}
    n_rel = (2 * cfg.window - 1) ** 2
    for i, (depth, heads) in enumerate(zip(cfg.depths, cfg.heads)):
        c = cfg.width(i)
        f = cfg.mlp_ratio * c

        def lin(a, b, n=depth):
            return {"w": ((n, a, b), ("w", a)), "b": ((n, b), "b")}
        blk = {"ln1_s": ((depth, c), "s"), "ln1_b": ((depth, c), "b"),
               "rel": ((depth, n_rel, heads), "t"),
               "ln2_s": ((depth, c), "s"), "ln2_b": ((depth, c), "b")}
        for name, (a, b) in {"qkv": (c, 3 * c), "proj": (c, c),
                             "fc1": (c, f), "fc2": (f, c)}.items():
            w = lin(a, b)
            blk[f"{name}_w"], blk[f"{name}_b"] = w["w"], w["b"]
        stage = {"blocks": blk}
        if i < len(cfg.depths) - 1:
            stage["merge"] = {"ln_s": ((4 * c,), "s"), "ln_b": ((4 * c,), "b"),
                              "w": ((4 * c, 2 * c), ("w", 4 * c))}
        out["stages"].append(stage)
    L, d, f = cfg.dec_layers, cfg.dec_d_model, cfg.dec_d_ff
    ce = cfg.width(len(cfg.depths) - 1)
    lay = {}
    for att in ("sa", "ca"):
        for proj in "qkvo":
            a = ce if att == "ca" and proj in "kv" else d
            lay[f"{att}_{proj}_w"] = ((L, a, d), ("w", a))
            lay[f"{att}_{proj}_b"] = ((L, d), "b")
    lay.update(fc1_w=((L, d, f), ("w", d)), fc1_b=((L, f), "b"),
               fc2_w=((L, f, d), ("w", f)), fc2_b=((L, d), "b"))
    for ln in ("sa", "ca", "ff"):
        lay[f"ln_{ln}_s"], lay[f"ln_{ln}_b"] = ((L, d), "s"), ((L, d), "b")
    out["dec"] = {"embed": ((cfg.vocab_size, d), "t"),
                  "pos": ((cfg.max_positions + cfg.pos_offset, d), "t"),
                  "ln_emb_s": ((d,), "s"), "ln_emb_b": ((d,), "b"),
                  "ln_f_s": ((d,), "s"), "ln_f_b": ((d,), "b"),
                  "layers": lay}
    return out


def init_params(cfg: NougatConfig, seed: int):
    """Seeded weights, made on the device in one call, in
    ``param_dtype``: linear weights N(0, 1/fan_in), biases N(0, 0.02^2),
    LayerNorm scales 1 + N(0, 0.1^2), tables N(0, 0.02^2)."""
    spec = param_shapes(cfg)
    leaves, tree = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    dtype = jnp.dtype(cfg.param_dtype)

    def make(key):
        out = []
        for k, (shape, kind) in zip(jax.random.split(key, len(leaves)),
                                    leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "s":
                z = 1.0 + 0.1 * z
            elif kind in ("b", "t"):
                z = 0.02 * z
            else:
                z = z * kind[1] ** -0.5
            out.append(z.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(jax.random.key(seed % 2 ** 32))


# ---------------------------------------------------------------------------
# Page images
# ---------------------------------------------------------------------------


def page_images(pages, cfg: NougatConfig) -> np.ndarray:
    """uint8 (len(pages), *image_hw): each page's token ids drawn in
    reading order, one ``glyph_cell`` per token, its ink a 4 x 4 bit
    pattern of a hash of the id (black on white); tokens past the last
    cell are not drawn."""
    H, W = cfg.image_hw
    ch, cw = cfg.glyph_cell
    rows, cols = H // ch, W // cw
    ids = np.zeros((len(pages), rows * cols), np.uint64)
    has = np.zeros(ids.shape, bool)
    for i, page in enumerate(pages):
        n = min(len(page), rows * cols)
        ids[i, :n] = page[:n]
        has[i, :n] = True
    code = ((ids * np.uint64(2654435761)) & np.uint64(0xFFFFFFFF)) >> \
        np.uint64(16)
    ink = ((code[..., None] >> np.arange(16, dtype=np.uint64)) & 1) \
        .astype(bool) & has[..., None]
    img = np.where(ink, np.uint8(0), np.uint8(255)).reshape(
        len(pages), rows, cols, 4, 4)
    img = img.repeat(ch // 4, axis=3).repeat(cw // 4, axis=4)
    img = img.transpose(0, 1, 3, 2, 4).reshape(len(pages), rows * ch,
                                               cols * cw)
    out = np.full((len(pages), H, W), 255, np.uint8)
    out[:, :rows * ch, :cols * cw] = img
    return out


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------


def relative_position_index(window: int) -> np.ndarray:
    """(w*w, w*w): the row of a window position pair's bias in the
    ((2w-1)^2, heads) table, (dr + w - 1) * (2w - 1) + (dc + w - 1)."""
    r, c = np.divmod(np.arange(window * window), window)
    dr = r[:, None] - r[None, :] + window - 1
    dc = c[:, None] - c[None, :] + window - 1
    return dr * (2 * window - 1) + dc


def shift_mask(h: int, w: int, window: int, shift: int) -> np.ndarray:
    """(windows, w*w, w*w) additive mask of a grid rolled up and left by
    ``shift``: 0 between positions of the same region of the unrolled
    grid's three row and three column bands, ``MASKED`` across."""
    bands = np.array([0] * (h - window) + [1] * (window - shift)
                     + [2] * shift)
    cbands = np.array([0] * (w - window) + [1] * (window - shift)
                      + [2] * shift)
    region = bands[:, None] * 3 + cbands[None, :]
    win = _windows(region[None, :, :, None], window)[0, ..., 0]
    return np.where(win[:, :, None] == win[:, None, :], 0.0,
                    MASKED).astype(np.float32)


def _windows(x, window: int):
    """(B, h, w, C) -> (B, windows, w*w, C), windows in row order."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // window, window, w // window, window, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, -1, window * window, c)


def _unwindows(x, window: int, h: int, w: int):
    b, _, _, c = x.shape
    x = x.reshape(b, h // window, w // window, window, window, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, w, c)


def _lin(x, w, b=None):
    y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    return y if b is None else y + b.astype(y.dtype)


def _block(x, bp, heads: int, window: int, shift: int, eps: float):
    """One Swin block over (B, h, w, C); ``shift`` 0 or window // 2."""
    b, h, w, c = x.shape
    dh = c // heads
    n = window * window
    y = layer_norm(x, bp["ln1_s"], bp["ln1_b"], eps)
    if shift:
        y = jnp.roll(y, (-shift, -shift), axis=(1, 2))
    qkv = _lin(_windows(y, window), bp["qkv_w"], bp["qkv_b"])
    qkv = qkv.reshape(b, -1, n, 3, heads, dh)
    q = qkv[..., 0, :, :] * dh ** -0.5
    s = jnp.einsum("bwnhd,bwmhd->bwhnm", q, qkv[..., 1, :, :],
                   preferred_element_type=jnp.float32)
    rel = bp["rel"].astype(jnp.float32)[relative_position_index(window)]
    s = s + rel.transpose(2, 0, 1)[None, None]
    if shift:
        s = s + jnp.asarray(shift_mask(h, w, window, shift))[None, :, None]
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bwhnm,bwmhd->bwnhd", p, qkv[..., 2, :, :])
    o = _lin(o.reshape(b, -1, n, c), bp["proj_w"], bp["proj_b"])
    o = _unwindows(o, window, h, w)
    if shift:
        o = jnp.roll(o, (shift, shift), axis=(1, 2))
    x = x + o
    y = layer_norm(x, bp["ln2_s"], bp["ln2_b"], eps)
    y = gelu(_lin(y, bp["fc1_w"], bp["fc1_b"]), approximate=False)
    return x + _lin(y, bp["fc2_w"], bp["fc2_b"])


def _merge(x, mp, eps: float):
    """2 x 2 patch merging: (B, h, w, C) -> (B, h/2, w/2, 2C)."""
    x = jnp.concatenate([x[:, 0::2, 0::2], x[:, 1::2, 0::2],
                         x[:, 0::2, 1::2], x[:, 1::2, 1::2]], axis=-1)
    return _lin(layer_norm(x, mp["ln_s"], mp["ln_b"], eps), mp["w"])


def encode(params, cfg: NougatConfig, images):
    """uint8 (B, H, W) page images -> (B, enc_tokens, C) memory."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = images.astype(jnp.float32) / 255.0
    x = jnp.stack([(x - m) / s for m, s in zip(PIXEL_MEAN, PIXEL_STD)], -1)
    b, H, W, _ = x.shape
    p = cfg.patch
    x = x.reshape(b, H // p, p, W // p, p, 3).transpose(0, 1, 3, 2, 4, 5)
    x = _lin(x.reshape(b, H // p, W // p, p * p * 3).astype(cdt),
             params["patch"]["w"], params["patch"]["b"])
    x = layer_norm(x, params["patch"]["ln_s"], params["patch"]["ln_b"],
                   cfg.norm_eps)
    for stage, heads, sp in zip(range(len(cfg.depths)), cfg.heads,
                                params["stages"]):
        h, w = cfg.grid(stage)
        win = cfg.window
        if h % win or w % win or min(h, w) <= win:
            raise ValueError(f"stage {stage}'s {h}x{w} grid does not tile "
                             f"into shifted {win}x{win} windows")
        pairs = jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[0] // 2, 2, *a.shape[1:]),
            sp["blocks"])

        def pair(x, bp, heads=heads, win=win):
            for i, shift in enumerate((0, win // 2)):
                x = _block(x, jax.tree_util.tree_map(lambda a: a[i], bp),
                           heads, win, shift, cfg.norm_eps)
            return x, None
        x, _ = jax.lax.scan(pair, x, pairs)
        if "merge" in sp:
            x = _merge(x, sp["merge"], cfg.norm_eps)
    return x.reshape(b, -1, x.shape[-1])


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------


def cross_kv(params, cfg: NougatConfig, memory):
    """Every layer's cross-attention keys and values of (B, n, C) memory:
    two (layers, B, n, d), each head's head_dim columns side by side."""
    lay = params["dec"]["layers"]

    def proj(w, bias):
        y = jnp.einsum("bnc,lcd->lbnd", memory, lay[w].astype(memory.dtype))
        return y + lay[bias][:, None, None].astype(y.dtype)
    return proj("ca_k_w", "ca_k_b"), proj("ca_v_w", "ca_v_b")


def _embed(dec, cfg: NougatConfig, tokens, positions):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = dec["embed"].astype(cdt)[tokens]
    if cfg.scale_embedding:
        x = x * math.sqrt(cfg.dec_d_model)
    x = x + dec["pos"].astype(cdt)[positions + cfg.pos_offset]
    return layer_norm(x, dec["ln_emb_s"], dec["ln_emb_b"], cfg.norm_eps)


def _query(y, lp, pre: str, heads: int):
    """The ``pre`` attention's queries of ``y``, (..., heads, head_dim),
    scaled by head_dim^-0.5."""
    q = _lin(y, lp[pre + "_q_w"], lp[pre + "_q_b"])
    q = q.reshape(*q.shape[:-1], heads, -1)
    return q * q.shape[-1] ** -0.5


def _attend_rows(q, ks, vs, bias):
    """One query per row: q (..., H, dh) against keys and values given
    as blocks of positions, each (..., block, H*dh), an additive bias
    over all the blocks' positions -> (..., H*dh). Every head's query
    is spread over its own dh columns of an (H, H*dh) block-diagonal
    matrix, so both products contract or emit the cache's rows as they
    are stored (one row of H*dh per position), each block where it
    lies: nothing is relaid out, sliced or concatenated but the scores.
    The output keeps each head's own block."""
    h, dh = q.shape[-2:]
    own = jnp.repeat(jnp.eye(h, dtype=q.dtype), dh, axis=1)
    qb = q.reshape(*q.shape[:-2], 1, h * dh) * own
    s = jnp.concatenate([jnp.einsum("...hc,...tc->...ht", qb, k,
                                    preferred_element_type=jnp.float32)
                         for k in ks], axis=-1) + bias
    p = jax.nn.softmax(s, axis=-1).astype(qb.dtype)
    o, at = 0.0, 0
    for v in vs:
        n = v.shape[-2]
        o = o + jnp.einsum("...ht,...tc->...hc", p[..., at:at + n], v,
                           preferred_element_type=jnp.float32)
        at += n
    return jnp.sum(o * own, axis=-2).astype(qb.dtype)


def _attend(q, k, v, bias):
    """Queries (B, T, H, dh) against k, v (B, S, H*dh), an additive
    float32 bias broadcast to (B, H, T, S) -> (B, T, H*dh)."""
    b, t, h, dh = q.shape
    k = k.reshape(b, -1, h, dh)
    v = v.reshape(b, -1, h, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) + bias
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, h * dh)


def _ffn(x, lp, eps):
    y = layer_norm(x, lp["ln_ff_s"], lp["ln_ff_b"], eps)
    y = gelu(_lin(y, lp["fc1_w"], lp["fc1_b"]), approximate=False)
    return x + _lin(y, lp["fc2_w"], lp["fc2_b"])


def _logits(dec, cfg: NougatConfig, x):
    x = layer_norm(x, dec["ln_f_s"], dec["ln_f_b"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", x, dec["embed"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def forward(params, cfg: NougatConfig, images, tokens):
    """Teacher-forced logits: uint8 (B, H, W) pages and (B, T) input
    tokens -> float32 (B, T, vocab), the decoder causal over T."""
    dec = params["dec"]
    xk, xv = cross_kv(params, cfg, encode(params, cfg, images))
    t = tokens.shape[1]
    x = _embed(dec, cfg, tokens, jnp.arange(t))
    h = cfg.dec_heads
    causal = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None],
                       0.0, NEG_INF)

    def layer(x, inp):
        lp, k_x, v_x = inp
        y = layer_norm(x, lp["ln_sa_s"], lp["ln_sa_b"], cfg.norm_eps)
        k, v = _lin(y, lp["sa_k_w"], lp["sa_k_b"]), _lin(y, lp["sa_v_w"],
                                                          lp["sa_v_b"])
        o = _attend(_query(y, lp, "sa", h), k, v, causal)
        x = x + _lin(o, lp["sa_o_w"], lp["sa_o_b"])
        y = layer_norm(x, lp["ln_ca_s"], lp["ln_ca_b"], cfg.norm_eps)
        o = _attend(_query(y, lp, "ca", h), k_x, v_x, 0.0)
        x = x + _lin(o, lp["ca_o_w"], lp["ca_o_b"])
        return _ffn(x, lp, cfg.norm_eps), None

    x, _ = jax.lax.scan(layer, x, (dec["layers"], xk, xv))
    return _logits(dec, cfg, x)


def loss(params, cfg: NougatConfig, batch):
    """Mean cross-entropy of ``batch["labels"]`` (B, T) given the pages
    and the input tokens."""
    logits = forward(params, cfg, batch["images"], batch["tokens"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# Serving: the two programs
# ---------------------------------------------------------------------------


def cross_rows(cfg: NougatConfig) -> int:
    """Rows of the cross-attention caches: ``enc_tokens`` rounded up to
    whole ``CROSS_ROWS``."""
    return -(-cfg.enc_tokens // CROSS_ROWS) * CROSS_ROWS


def slot_caches(cfg: NougatConfig):
    """Zeroed page-slot caches, one row of d (every head's head_dim side
    by side) per position, one array per decoder layer: cross-attention
    ``xk``/``xv`` (slots, ``cross_rows``, d), the first ``enc_tokens``
    rows a page's, and self-attention ``ck``/``cv`` split further into
    blocks of ``KV_BLOCK`` positions, (slots, KV_BLOCK, d) each, so a
    step reads whole arrays and writes one."""
    cdt = jnp.dtype(cfg.compute_dtype)
    s, d, L = cfg.decode_slots, cfg.dec_d_model, cfg.dec_layers
    blocks = cfg.cache_len // KV_BLOCK

    def own():
        return [[jnp.zeros((s, KV_BLOCK, d), cdt) for _ in range(blocks)]
                for _ in range(L)]

    def cross():
        return [jnp.zeros((s, cross_rows(cfg), d), cdt) for _ in range(L)]
    return {"xk": cross(), "xv": cross(), "ck": own(), "cv": own()}


def decode_state(cfg: NougatConfig, caches: dict, n, cap_slot, cap_step):
    """A wave's decode state (``decode_step``) over the self-attention
    blocks of ``caches``: every slot at BOS, slot i decoding ``n[i]``
    steps, the logits of slot ``cap_slot[j]`` at step ``cap_step[j]``
    kept (-1: none)."""
    slots = len(n)
    tokens = jnp.zeros((slots, cfg.cache_len + 1), jnp.int32)
    return {"ck": caches["ck"], "cv": caches["cv"],
            "tok": jnp.full((slots,), cfg.bos_id, jnp.int32),
            "tokens": tokens.at[:, 0].set(cfg.bos_id),
            "n": jnp.asarray(n, jnp.int32),
            "steps": jnp.zeros((slots,), jnp.int32),
            "cap_slot": jnp.asarray(cap_slot, jnp.int32),
            "cap_step": jnp.asarray(cap_step, jnp.int32),
            "cap": jnp.zeros((len(cap_slot), cfg.vocab_size), jnp.float32)}


def encode_into_slots(params, cfg: NougatConfig, images, xk, xv, slot):
    """A chunk of pages through the encoder; their cross-attention K/V
    written into slots ``slot`` onward of each layer's ``xk``/``xv``."""
    k, v = cross_kv(params, cfg, encode(params, cfg, images))
    at = (slot, 0, 0)
    return ([jax.lax.dynamic_update_slice(a, b.astype(a.dtype), at)
             for a, b in zip(xk, k)],
            [jax.lax.dynamic_update_slice(a, b.astype(a.dtype), at)
             for a, b in zip(xv, v)])


def uses_attention_kernel(cfg: NougatConfig,
                          force_kernel: bool = False) -> bool:
    """Whether ``decode_step`` attends through
    ``kernels.decode_attention`` (its dispatch rule, at the decoder's
    row width)."""
    return decode_attention.uses_kernel(cfg.dec_d_model, force_kernel)


def decode_step(params, cfg: NougatConfig, state: dict, xk, xv, t,
                span: int, force_kernel: bool = False):
    """One greedy step, at position t < ``span``, for every slot at once,
    reading the self-attention cache's first ``span`` positions (whole
    blocks). It embeds ``state["tok"]`` at t, writes its keys and values
    at t, and takes the argmax of its logits as the next token:
    ``tokens[:, t + 1]``. A slot is live while t < ``n``; ``steps``
    counts its live steps. Logits of slot ``cap_slot[j]``, at step
    ``cap_step[j]``, land in ``cap[j]``. Through the attention kernel
    (``uses_attention_kernel``) a slot that is not live reads no K/V
    and attends to nothing; its token is never read.

    ``state``: ``ck``, ``cv`` (the self-attention cache, as
    ``slot_caches``), ``tok`` (slots,), ``tokens`` (slots,
    cache_len + 1), ``n``, ``steps`` (slots,), ``cap_slot``,
    ``cap_step`` (captures,), ``cap`` (captures, vocab) float32."""
    dec = params["dec"]
    lay = dec["layers"]
    h = cfg.dec_heads
    blocks = span // KV_BLOCK
    last = blocks - 1
    at = (0, t - last * KV_BLOCK, 0)
    if uses_attention_kernel(cfg, force_kernel):
        live = decode_attention.live_slots(t < state["n"])

        def attend(q, ks, vs, end):
            return decode_attention.decode_attention(q, ks, vs, live, end)
    else:
        def attend(q, ks, vs, end):
            rows = jnp.arange(sum(k.shape[1] for k in ks))
            return _attend_rows(q, ks, vs,
                                jnp.where(rows <= end, 0.0, NEG_INF))
    x = _embed(dec, cfg, state["tok"], t)
    new = {"ck": [], "cv": []}
    for l in range(cfg.dec_layers):
        lp = jax.tree_util.tree_map(lambda a: a[l], lay)
        y = layer_norm(x, lp["ln_sa_s"], lp["ln_sa_b"], cfg.norm_eps)
        for name, proj in (("ck", "sa_k"), ("cv", "sa_v")):
            row = _lin(y, lp[proj + "_w"], lp[proj + "_b"])
            own = list(state[name][l])
            own[last] = jax.lax.dynamic_update_slice(
                own[last], row[:, None].astype(own[last].dtype), at)
            new[name].append(own)
        o = attend(_query(y, lp, "sa", h), new["ck"][l][:blocks],
                   new["cv"][l][:blocks], t)
        x = x + _lin(o, lp["sa_o_w"], lp["sa_o_b"])
        y = layer_norm(x, lp["ln_ca_s"], lp["ln_ca_b"], cfg.norm_eps)
        o = attend(_query(y, lp, "ca", h), [xk[l]], [xv[l]],
                   cfg.enc_tokens - 1)
        x = _ffn(x + _lin(o, lp["ca_o_w"], lp["ca_o_b"]), lp, cfg.norm_eps)
    logits = _logits(dec, cfg, x)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    hit = (state["cap_step"] == t)[:, None]
    return dict(
        state, **new, tok=nxt,
        tokens=jax.lax.dynamic_update_slice(state["tokens"], nxt[:, None],
                                            (0, t + 1)),
        steps=state["steps"] + (t < state["n"]).astype(jnp.int32),
        cap=jnp.where(hit, logits[state["cap_slot"]], state["cap"]))


@functools.lru_cache(maxsize=None)
def serving_programs(cfg: NougatConfig, force_kernel: bool = False):
    """(``parse_encode``, ``parse_decode``): the jitted programs, the
    caches they write donated. ``parse_decode(params, state, xk, xv, t,
    span=...)`` takes ``span`` static: one program per cache block."""
    def parse_encode(params, images, xk, xv, slot):
        return encode_into_slots(params, cfg, images, xk, xv, slot)

    def parse_decode(params, state, xk, xv, t, span):
        return decode_step(params, cfg, state, xk, xv, t, span,
                           force_kernel)

    return (jax.jit(parse_encode, donate_argnums=(2, 3)),
            jax.jit(parse_decode, donate_argnums=(1,),
                    static_argnames=("span",)))
