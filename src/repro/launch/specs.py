"""Cell factory: (arch × shape) -> (step_fn, abstract inputs, shardings).

Every one of the 40 assigned cells (+ the paper's own router/parser cells)
is materialized here as a jit-able step function plus weak-type-correct
ShapeDtypeStruct stand-ins for all inputs (parameters, optimizer state,
batches, KV caches), so lowering a cell allocates no full-size array.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import Param, abstractify, is_param, unwrap
from repro.configs.base import (ArchConfig, EncoderConfig, GNNConfig,
                                LMConfig, RecsysConfig, ShapeConfig,
                                get_config)
from repro.distributed.meshrules import AxisRules
from repro.models import encoder as enc_lib
from repro.models import nougat as nougat_lib
from repro.models import transformer as lm_lib
from repro.models.attention import KVCache
from repro.models.gnn import equiformer as eq_lib
from repro.models.gnn import sampler as sampler_lib
from repro.models.recsys import models as rs_lib
from repro.optim import adafactor, adamw, apply_updates, chain_clip


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def _optimizer_for(arch: ArchConfig):
    if arch.arch_id.startswith("grok"):
        return adafactor(1e-4), "adafactor"
    return chain_clip(adamw(3e-4, weight_decay=0.1), 1.0), "adamw"


def _opt_state_shardings(rules: AxisRules, params, kind: str):
    """Optimizer-state shardings mirror the param layout + ZeRO 'data'."""
    if rules is None:
        return None
    if kind == "adamw":
        t = jax.tree_util.tree_map(
            lambda p: rules.zero_sharding_for(p.axes, p.value.shape),
            params, is_leaf=is_param)
        return {"m": t, "v": t}
    # adafactor: factored leaves {"vr","vc"} else {"v"}
    def leaf(p):
        shp = p.value.shape
        if len(shp) >= 2 and shp[-1] >= 128 and shp[-2] >= 128:
            return {"vr": rules.sharding_for(p.axes[:-1], shp[:-1]),
                    "vc": rules.sharding_for(p.axes[:-2] + p.axes[-1:],
                                             shp[:-2] + shp[-1:])}
        return {"v": rules.zero_sharding_for(p.axes, shp)}

    return {"v": jax.tree_util.tree_map(leaf, params, is_leaf=is_param)}


def _abstract_opt_state(opt, params_raw):
    return jax.eval_shape(opt.init, params_raw)


def _batch_shardings(rules: AxisRules, axes_map: dict, batch: dict):
    if rules is None:
        return None
    return {k: rules.sharding_for(axes_map[k], v.shape)
            for k, v in batch.items()}


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    kind: str                         # train | prefill | decode | serve
    fn: Callable
    args: tuple                       # abstract or concrete
    in_shardings: Any = None          # tree matching args (or None)
    donate_argnums: tuple = ()
    note: str = ""


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _lm_train_cell(arch: ArchConfig, shape: ShapeConfig, rules, abstract,
                   seed=0) -> Cell:
    cfg: LMConfig = arch.model
    opt, kind = _optimizer_for(arch)
    params = lm_lib.init_lm(cfg, seed, abstract=abstract)
    params_raw = unwrap(params)
    opt_state = (_abstract_opt_state(opt, params_raw) if abstract
                 else opt.init(params_raw))

    def train_step(params_raw, opt_state, step, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm_lib.lm_loss(p, cfg, batch), has_aux=True)(params_raw)
        updates, opt_state = opt.update(grads, opt_state, params_raw, step)
        params_raw = apply_updates(params_raw, updates)
        return params_raw, opt_state, loss

    b, s = shape["global_batch"], shape["seq_len"]
    if abstract:
        batch = {"tokens": _sds((b, s), jnp.int32),
                 "labels": _sds((b, s), jnp.int32)}
    else:
        rng = np.random.RandomState(seed)
        toks = rng.randint(0, cfg.vocab_size, size=(b, s + 1)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks[:, :-1]),
                 "labels": jnp.asarray(toks[:, 1:])}
    in_sh = None
    if rules is not None:
        p_sh = rules.param_shardings(params)
        in_sh = (p_sh, _opt_state_shardings(rules, params, kind),
                 rules.sharding_for((), ()),
                 _batch_shardings(rules, {"tokens": ("batch", "seq"),
                                          "labels": ("batch", "seq")}, batch))
    step0 = _sds((), jnp.int32) if abstract else jnp.asarray(0)
    return Cell(arch.arch_id, shape.name, "train", train_step,
                (params_raw, opt_state, step0, batch), in_sh,
                donate_argnums=(0, 1))


def _lm_prefill_cell(arch, shape, rules, abstract, seed=0) -> Cell:
    cfg: LMConfig = arch.model
    params_raw = unwrap(lm_lib.init_lm(cfg, seed, abstract=abstract))
    b, s = shape["global_batch"], shape["seq_len"]

    def prefill_step(params_raw, tokens):
        return lm_lib.prefill(params_raw, cfg, tokens)

    tokens = (_sds((b, s), jnp.int32) if abstract else
              jnp.asarray(np.random.RandomState(seed).randint(
                  0, cfg.vocab_size, size=(b, s)), jnp.int32))
    in_sh = None
    if rules is not None:
        params = lm_lib.init_lm(cfg, seed, abstract=True)
        in_sh = (rules.param_shardings(params),
                 rules.sharding_for(("batch", "seq"), (b, s)))
    return Cell(arch.arch_id, shape.name, "prefill", prefill_step,
                (params_raw, tokens), in_sh)


def _lm_decode_cell(arch, shape, rules, abstract, seed=0) -> Cell:
    cfg: LMConfig = arch.model
    params_raw = unwrap(lm_lib.init_lm(cfg, seed, abstract=abstract))
    b, s = shape["global_batch"], shape["seq_len"]
    cdt = jnp.dtype(cfg.compute_dtype)

    def serve_step(params_raw, tokens, cache, pos):
        return lm_lib.decode_step(params_raw, cfg, tokens, cache, pos)

    if abstract:
        tokens = _sds((b, 1), jnp.int32)
        cache = KVCache.abstract(cfg.n_layers, b, s, cfg.n_kv_heads,
                                 cfg.head_dim, cdt)
        pos = _sds((), jnp.int32)
    else:
        tokens = jnp.zeros((b, 1), jnp.int32)
        cache = KVCache.zeros(cfg.n_layers, b, s, cfg.n_kv_heads,
                              cfg.head_dim, cdt)
        pos = jnp.asarray(s - 1)
    in_sh = None
    if rules is not None:
        cache_axes = ("layers", "batch", "kv_seq", "kv_heads", "d_head")
        cache_shape = (cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim)
        cache_sh = KVCache(rules.sharding_for(cache_axes, cache_shape),
                           rules.sharding_for(cache_axes, cache_shape))
        params = lm_lib.init_lm(cfg, seed, abstract=True)
        in_sh = (rules.param_shardings(params),
                 rules.sharding_for(("batch", None), (b, 1)),
                 cache_sh, rules.sharding_for((), ()))
    return Cell(arch.arch_id, shape.name, "decode", serve_step,
                (params_raw, tokens, cache, pos), in_sh,
                donate_argnums=(2,))


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

GNN_DATASETS = {
    # shape -> (d_in, n_out, classification?)
    "full_graph_sm": (1433, 7, True),        # Cora
    "minibatch_lg": (602, 41, True),         # Reddit (sampled)
    "ogb_products": (100, 47, True),         # ogbn-products
    "molecule": (16, 1, False),              # batched small molecules
}


def _gnn_dims(shape: ShapeConfig) -> tuple[int, int]:
    from repro.common import round_up
    if shape.name == "minibatch_lg":
        n = sampler_lib.static_node_count(shape["batch_nodes"],
                                          [shape["fanout0"], shape["fanout1"]])
        e = sampler_lib.static_edge_count(shape["batch_nodes"],
                                          [shape["fanout0"], shape["fanout1"]])
        return n, e
    if shape.name == "molecule":
        return shape["n_nodes"] * shape["batch"], shape["n_edges"] * shape["batch"]
    # full-graph cells: pad nodes/edges to a 512 multiple so the mesh can
    # shard them (61,859,140 % 256 != 0 would force full replication —
    # 15 TB/dev). Padding edges are zero-length self-loops, which the
    # equivariance mask already drops from message passing.
    return (round_up(shape["n_nodes"], 512), round_up(shape["n_edges"], 512))


def _gnn_train_cell(arch, shape, rules, abstract, seed=0) -> Cell:
    d_in, n_out, is_cls = GNN_DATASETS[shape.name]
    cfg: GNNConfig = dataclasses.replace(arch.model, d_in=d_in, n_out=n_out)
    opt, kind = _optimizer_for(arch)
    params = eq_lib.init_equiformer(cfg, seed, abstract=abstract)
    params_raw = unwrap(params)
    opt_state = (_abstract_opt_state(opt, params_raw) if abstract
                 else opt.init(params_raw))
    n, e = _gnn_dims(shape)

    def train_step(params_raw, opt_state, step, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: eq_lib.equiformer_loss(p, cfg, batch),
            has_aux=True)(params_raw)
        updates, opt_state = opt.update(grads, opt_state, params_raw, step)
        return apply_updates(params_raw, updates), opt_state, loss

    lbl_dtype = jnp.int32 if is_cls else jnp.float32
    if shape.name == "molecule":
        lbl_shape = (shape["batch"], n_out)
    else:
        lbl_shape = (n,)
    if abstract:
        batch = {"pos": _sds((n, 3), jnp.float32),
                 "src": _sds((e,), jnp.int32),
                 "dst": _sds((e,), jnp.int32),
                 "node_feat": _sds((n, d_in), jnp.float32),
                 "labels": _sds(lbl_shape, lbl_dtype)}
        if shape.name == "molecule":
            batch["graph_ids"] = _sds((n,), jnp.int32)
    else:
        rng = np.random.RandomState(seed)
        batch = {"pos": jnp.asarray(rng.randn(n, 3), jnp.float32),
                 "src": jnp.asarray(rng.randint(0, n, e), jnp.int32),
                 "dst": jnp.asarray(rng.randint(0, n, e), jnp.int32),
                 "node_feat": jnp.asarray(rng.randn(n, d_in), jnp.float32),
                 "labels": (jnp.asarray(rng.randint(0, n_out, lbl_shape),
                                        jnp.int32) if is_cls else
                            jnp.asarray(rng.randn(*lbl_shape), jnp.float32))}
        if shape.name == "molecule":
            batch["graph_ids"] = jnp.repeat(
                jnp.arange(shape["batch"], dtype=jnp.int32), shape["n_nodes"])
    if shape.name == "molecule":
        batch["n_graphs"] = shape["batch"]

    def train_step_static(params_raw, opt_state, step, batch):
        if shape.name == "molecule":
            batch = dict(batch, n_graphs=shape["batch"])
        return train_step(params_raw, opt_state, step, batch)

    sharded_batch = {k: v for k, v in batch.items() if k != "n_graphs"}
    in_sh = None
    if rules is not None:
        axes_map = {"pos": ("nodes", None), "src": ("edges",),
                    "dst": ("edges",), "node_feat": ("nodes", "d_feat"),
                    "labels": ("graphs", None) if shape.name == "molecule"
                    else ("nodes",),
                    "graph_ids": ("nodes",)}
        in_sh = (rules.param_shardings(params),
                 _opt_state_shardings(rules, params, kind),
                 rules.sharding_for((), ()),
                 _batch_shardings(rules, axes_map, sharded_batch))
    step0 = _sds((), jnp.int32) if abstract else jnp.asarray(0)
    return Cell(arch.arch_id, shape.name, "train", train_step_static,
                (params_raw, opt_state, step0, sharded_batch), in_sh,
                donate_argnums=(0, 1), note=f"N={n} E={e}")


# ---------------------------------------------------------------------------
# Recsys cells
# ---------------------------------------------------------------------------


def _recsys_batch(cfg: RecsysConfig, b: int, abstract, seed=0):
    if abstract:
        batch = {"sparse": _sds((b, cfg.n_sparse), jnp.int32),
                 "labels": _sds((b,), jnp.float32)}
        if cfg.kind == "dlrm":
            batch["dense"] = _sds((b, cfg.n_dense), jnp.float32)
        if cfg.kind == "dien":
            t = cfg.seq_len
            batch.update(hist=_sds((b, t), jnp.int32),
                         hist_cat=_sds((b, t), jnp.int32),
                         hist_mask=_sds((b, t), jnp.float32),
                         target=_sds((b,), jnp.int32),
                         target_cat=_sds((b,), jnp.int32))
        return batch
    rng = np.random.RandomState(seed)
    sparse = np.stack([rng.randint(0, v, b) for v in cfg.vocab_sizes], 1)
    batch = {"sparse": jnp.asarray(sparse, jnp.int32),
             "labels": jnp.asarray(rng.rand(b) < 0.3, jnp.float32)}
    if cfg.kind == "dlrm":
        batch["dense"] = jnp.asarray(rng.randn(b, cfg.n_dense), jnp.float32)
    if cfg.kind == "dien":
        t = cfg.seq_len
        v0, v1 = cfg.vocab_sizes[0], sum(cfg.vocab_sizes)
        batch.update(
            hist=jnp.asarray(rng.randint(0, v0, (b, t)), jnp.int32),
            hist_cat=jnp.asarray(rng.randint(v0, v1, (b, t)), jnp.int32),
            hist_mask=jnp.ones((b, t), jnp.float32),
            target=jnp.asarray(rng.randint(0, v0, b), jnp.int32),
            target_cat=jnp.asarray(rng.randint(v0, v1, b), jnp.int32))
    return batch


_RS_AXES = {"sparse": ("batch", "fields"), "labels": ("batch",),
            "dense": ("batch", None), "hist": ("batch", None),
            "hist_cat": ("batch", None), "hist_mask": ("batch", None),
            "target": ("batch",), "target_cat": ("batch",),
            "user_query": ("batch", "embed_dim")}


def _recsys_cell(arch, shape, rules, abstract, seed=0) -> Cell:
    cfg: RecsysConfig = arch.model
    params = rs_lib.init_recsys(cfg, seed, abstract=abstract)
    params_raw = unwrap(params)

    if shape.name == "retrieval_cand":
        n_cand = min(shape["n_candidates"], int(sum(cfg.vocab_sizes)))

        k_top = min(100, n_cand)

        def retrieval_step(params_raw, batch):
            return rs_lib.recsys_retrieval(
                params_raw, cfg, dict(batch, n_candidates=n_cand), k=k_top)

        q = (_sds((shape["batch"], cfg.embed_dim), jnp.float32) if abstract
             else jnp.asarray(np.random.RandomState(seed).randn(
                 shape["batch"], cfg.embed_dim), jnp.float32))
        batch = {"user_query": q}
        in_sh = None
        if rules is not None:
            in_sh = (rules.param_shardings(params),
                     _batch_shardings(rules, _RS_AXES, batch))
        return Cell(arch.arch_id, shape.name, "serve", retrieval_step,
                    (params_raw, batch), in_sh, note=f"n_cand={n_cand}")

    b = shape["batch"]
    batch = _recsys_batch(cfg, b, abstract, seed)
    if shape.kind == "train":
        opt, kind = _optimizer_for(arch)
        opt_state = (_abstract_opt_state(opt, params_raw) if abstract
                     else opt.init(params_raw))

        def train_step(params_raw, opt_state, step, batch):
            (loss, _), grads = jax.value_and_grad(
                lambda p: rs_lib.recsys_loss(p, cfg, batch),
                has_aux=True)(params_raw)
            updates, opt_state = opt.update(grads, opt_state, params_raw,
                                            step)
            return apply_updates(params_raw, updates), opt_state, loss

        in_sh = None
        if rules is not None:
            in_sh = (rules.param_shardings(params),
                     _opt_state_shardings(rules, params, kind),
                     rules.sharding_for((), ()),
                     _batch_shardings(rules, _RS_AXES, batch))
        step0 = _sds((), jnp.int32) if abstract else jnp.asarray(0)
        return Cell(arch.arch_id, shape.name, "train", train_step,
                    (params_raw, opt_state, step0, batch), in_sh,
                    donate_argnums=(0, 1))

    serve_batch = {k: v for k, v in batch.items() if k != "labels"}

    def serve_step(params_raw, batch):
        return rs_lib.recsys_scores(params_raw, cfg, batch)

    in_sh = None
    if rules is not None:
        in_sh = (rules.param_shardings(params),
                 _batch_shardings(rules, _RS_AXES, serve_batch))
    return Cell(arch.arch_id, shape.name, "serve", serve_step,
                (params_raw, serve_batch), in_sh)


# ---------------------------------------------------------------------------
# AdaParse router cells (the paper's own model)
# ---------------------------------------------------------------------------


def _router_cell(arch, shape, rules, abstract, seed=0) -> Cell:
    from repro.core.dpo import dpo_loss
    from repro.core.router import make_route_step

    cfg: EncoderConfig = arch.model
    params = enc_lib.init_encoder(cfg, seed, abstract=abstract)
    params_raw = unwrap(params)
    b = shape["global_batch"]
    s = min(shape["seq_len"], cfg.max_len)

    def mk_tok(bb):
        if abstract:
            return _sds((bb, s), jnp.int32), _sds((bb, s), jnp.float32)
        rng = np.random.RandomState(seed)
        return (jnp.asarray(rng.randint(2, cfg.vocab_size, (bb, s)),
                            jnp.int32), jnp.ones((bb, s), jnp.float32))

    if shape.name.startswith("sft"):
        opt, kind = _optimizer_for(arch)
        opt_state = (_abstract_opt_state(opt, params_raw) if abstract
                     else opt.init(params_raw))
        toks, mask = mk_tok(b)
        tgt = (_sds((b, cfg.n_outputs), jnp.float32) if abstract
               else jnp.full((b, cfg.n_outputs), 0.5, jnp.float32))
        batch = {"tokens": toks, "mask": mask, "targets": tgt}

        def train_step(params_raw, opt_state, step, batch):
            loss, grads = jax.value_and_grad(
                lambda p: enc_lib.regression_loss(p, cfg, batch))(params_raw)
            updates, opt_state = opt.update(grads, opt_state, params_raw,
                                            step)
            return apply_updates(params_raw, updates), opt_state, loss

        in_sh = None
        if rules is not None:
            axes = {"tokens": ("batch", "seq"), "mask": ("batch", "seq"),
                    "targets": ("batch", None)}
            in_sh = (rules.param_shardings(params),
                     _opt_state_shardings(rules, params, kind),
                     rules.sharding_for((), ()),
                     _batch_shardings(rules, axes, batch))
        step0 = _sds((), jnp.int32) if abstract else jnp.asarray(0)
        return Cell(arch.arch_id, shape.name, "train", train_step,
                    (params_raw, opt_state, step0, batch), in_sh,
                    donate_argnums=(0, 1))

    if shape.name.startswith("dpo"):
        opt, kind = _optimizer_for(arch)
        opt_state = (_abstract_opt_state(opt, params_raw) if abstract
                     else opt.init(params_raw))
        tp, mp = mk_tok(b)
        tn, mn = mk_tok(b)
        batch = {"tok_pos": tp, "mask_pos": mp, "tok_neg": tn,
                 "mask_neg": mn}

        def train_step(params_raw, ref_raw, opt_state, step, batch):
            loss, grads = jax.value_and_grad(
                lambda p: dpo_loss(p, ref_raw, cfg, batch))(params_raw)
            updates, opt_state = opt.update(grads, opt_state, params_raw,
                                            step)
            return apply_updates(params_raw, updates), opt_state, loss

        in_sh = None
        if rules is not None:
            axes = {k: ("batch", "seq") for k in batch}
            p_sh = rules.param_shardings(params)
            in_sh = (p_sh, p_sh,
                     _opt_state_shardings(rules, params, kind),
                     rules.sharding_for((), ()),
                     _batch_shardings(rules, axes, batch))
        step0 = _sds((), jnp.int32) if abstract else jnp.asarray(0)
        return Cell(arch.arch_id, shape.name, "train", train_step,
                    (params_raw, params_raw, opt_state, step0, batch),
                    in_sh, donate_argnums=(0, 2))

    # route_*: the production fused route step (paper-representative cell)
    alpha = 0.05
    route_step = make_route_step(cfg, alpha)
    toks, mask = mk_tok(b)
    valid = (_sds((b,), jnp.float32) if abstract
             else jnp.ones((b,), jnp.float32))
    in_sh = None
    if rules is not None:
        in_sh = (rules.param_shardings(params),
                 rules.sharding_for(("batch", "seq"), (b, s)),
                 rules.sharding_for(("batch", "seq"), (b, s)),
                 rules.sharding_for(("batch",), (b,)))
    return Cell(arch.arch_id, shape.name, "serve", route_step,
                (params_raw, toks, mask, valid), in_sh,
                note=f"alpha={alpha}")


# ---------------------------------------------------------------------------
# Nougat parser cells
# ---------------------------------------------------------------------------


def _nougat_cell(arch, shape, rules, abstract, seed=0) -> Cell:
    """Training on page images, or one of the two serving programs,
    with ``global_batch`` pages (the decode step: slots)."""
    b = shape["global_batch"]
    cfg = dataclasses.replace(arch.model, decode_slots=b)

    def make(fn):
        return jax.eval_shape(fn) if abstract else fn()

    params = make(lambda: nougat_lib.init_params(cfg, seed))
    images = make(lambda: jnp.asarray(nougat_lib.page_images(
        [np.arange(10, 10 + 64 * (i + 1)) for i in range(b)], cfg)))
    if shape.kind == "train":
        t = min(shape["dec_len"], cfg.cache_len)
        opt, kind = _optimizer_for(arch)
        opt_state = make(lambda: opt.init(params))
        toks = make(lambda: jnp.zeros((b, t), jnp.int32))
        batch = {"images": images, "tokens": toks, "labels": toks}

        def train_step(params, opt_state, step, batch):
            loss, grads = jax.value_and_grad(
                lambda p: nougat_lib.loss(p, cfg, batch))(params)
            updates, opt_state = opt.update(grads, opt_state, params, step)
            return apply_updates(params, updates), opt_state, loss

        step0 = _sds((), jnp.int32) if abstract else jnp.asarray(0)
        return Cell(arch.arch_id, shape.name, "train", train_step,
                    (params, opt_state, step0, batch),
                    donate_argnums=(0, 1))
    caches = make(lambda: nougat_lib.slot_caches(cfg))
    slot = _sds((), jnp.int32) if abstract else jnp.asarray(0, jnp.int32)
    if shape.name == "parse_encode":
        return Cell(arch.arch_id, shape.name, "serve",
                    lambda p, im, xk, xv, at: nougat_lib.encode_into_slots(
                        p, cfg, im, xk, xv, at),
                    (params, images, caches["xk"], caches["xv"], slot),
                    donate_argnums=(2, 3))
    state = make(lambda: nougat_lib.decode_state(
        cfg, nougat_lib.slot_caches(cfg), [min(16, cfg.cache_len)] * b,
        [0], [0]))
    return Cell(arch.arch_id, shape.name, "decode",
                lambda p, st, xk, xv, t: nougat_lib.decode_step(
                    p, cfg, st, xk, xv, t, cfg.cache_len),
                (params, state, caches["xk"], caches["xv"], slot),
                donate_argnums=(1,))


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------


def build_cell(arch_id: str, shape_name: str, rules: AxisRules | None = None,
               abstract: bool = True, reduced: bool = False,
               seed: int = 0, model_override=None) -> Cell:
    arch = get_config(arch_id)
    if model_override is not None:
        arch = dataclasses.replace(arch, model=model_override)
    if reduced:
        arch = arch.reduced()
        shape = _reduce_shape(arch.family, arch.shape(shape_name))
    else:
        shape = arch.shape(shape_name)
    if shape_name in arch.skips and not reduced:
        raise ValueError(f"{arch_id}/{shape_name} skipped: "
                         f"{arch.skips[shape_name]}")
    fam = arch.family
    if fam == "lm":
        if shape.kind == "train":
            return _lm_train_cell(arch, shape, rules, abstract, seed)
        if shape.kind == "prefill":
            return _lm_prefill_cell(arch, shape, rules, abstract, seed)
        return _lm_decode_cell(arch, shape, rules, abstract, seed)
    if fam == "gnn":
        return _gnn_train_cell(arch, shape, rules, abstract, seed)
    if fam == "recsys":
        return _recsys_cell(arch, shape, rules, abstract, seed)
    if fam == "encoder":
        return _router_cell(arch, shape, rules, abstract, seed)
    if fam == "nougat":
        return _nougat_cell(arch, shape, rules, abstract, seed)
    raise ValueError(fam)


def _reduce_shape(family: str, shape: ShapeConfig) -> ShapeConfig:
    """Shrink a workload cell for CPU smoke tests (same kind/topology)."""
    d = dict(shape.dims)
    if family in ("lm", "encoder", "nougat"):
        if "seq_len" in d:
            d["seq_len"] = min(d["seq_len"], 64)
        if "global_batch" in d:
            d["global_batch"] = min(d["global_batch"], 4)
        if "dec_len" in d:
            d["dec_len"] = min(d["dec_len"], 16)
    elif family == "gnn":
        scale = {"full_graph_sm": dict(n_nodes=64, n_edges=256, d_feat=16),
                 "minibatch_lg": dict(n_nodes=0, n_edges=0, batch_nodes=8,
                                      fanout0=3, fanout1=2),
                 "ogb_products": dict(n_nodes=128, n_edges=512, d_feat=16),
                 "molecule": dict(n_nodes=6, n_edges=12, batch=4)}
        d.update(scale[shape.name])
    elif family == "recsys":
        if "batch" in d:
            d["batch"] = min(d["batch"], 16)
        if "n_candidates" in d:
            d["n_candidates"] = min(d["n_candidates"], 64)
    return ShapeConfig(shape.name, shape.kind, d, shape.note)
