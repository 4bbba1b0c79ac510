"""Pallas-TPU fused edge-GEMM + segment-scatter (GNN message passing).

out[dst_e] += (x_src[e] @ W)   for edges e, with dst SORTED ascending.

Grid: (n_edge_blocks,) sequential. Each step:
  1. MXU GEMM: (block_e, D_in) edge-source tile @ W -> (block_e, D_out)
  2. segment-reduce by dst within the tile + dynamic accumulate-stores
     into the output rows; a carried SMEM cell remembers the last dst row
     so partial sums crossing tile boundaries combine correctly.

This is the taxonomy's fused gather-GEMM-scatter regime (FusedMM /
GE-SpMM) adapted to TPU: the gather of x[src] stays an XLA gather (TPU
has no per-row HBM gather inside a kernel without scalar-prefetch DMA,
which interpret mode can't model faithfully), and the kernel fuses the
FLOP-heavy GEMM with the scatter so messages never round-trip to HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segmm_kernel(xg_ref, w_ref, dst_ref, out_ref, *, block_e: int,
                  n_edges: int, n_nodes: int):
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = xg_ref[...].astype(jnp.float32)             # (block_e, Din)
    w = w_ref[...].astype(jnp.float32)              # (Din, Dout)
    msg = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    rows = bi * block_e + jax.lax.iota(jnp.int32, block_e)
    valid = rows < n_edges
    dst = dst_ref[...]

    # accumulate runs of equal dst: since dst is sorted, each tile touches
    # a contiguous node range; do per-row accumulate-stores.
    def body(i, _):
        @pl.when(valid[i])
        def _acc():
            d = dst[i]
            cur = out_ref[pl.dslice(d, 1), :]
            row = jax.lax.dynamic_slice_in_dim(msg, i, 1, axis=0)
            out_ref[pl.dslice(d, 1), :] = cur + row.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, block_e, body, 0)


@functools.partial(jax.jit, static_argnames=("n_nodes", "block_e",
                                             "interpret"))
def segment_matmul_kernel(x_gathered, w, dst_sorted, *, n_nodes: int,
                          block_e: int = 256, interpret: bool):
    """x_gathered (E, Din) = x[src] pre-gathered; w (Din, Dout);
    dst_sorted (E,) int32 ascending. Returns (n_nodes, Dout) fp32."""
    e, d_in = x_gathered.shape
    d_out = w.shape[1]
    block_e = min(block_e, e)
    pad = (-e) % block_e
    if pad:
        x_gathered = jnp.pad(x_gathered, ((0, pad), (0, 0)))
        dst_sorted = jnp.pad(dst_sorted, (0, pad))
    grid = ((e + pad) // block_e,)
    kern = functools.partial(_segmm_kernel, block_e=block_e, n_edges=e,
                             n_nodes=n_nodes)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, d_in), lambda i: (i, 0)),
            pl.BlockSpec((d_in, d_out), lambda i: (0, 0)),
            pl.BlockSpec((block_e,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((n_nodes, d_out), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_nodes, d_out), jnp.float32),
        interpret=interpret,
    )(x_gathered, w, dst_sorted.astype(jnp.int32))
