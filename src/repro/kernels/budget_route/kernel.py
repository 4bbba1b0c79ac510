"""Pallas-TPU budget-route: threshold-select + compact-gather.

The AdaParse scheduling op (App. C): given per-document improvement
scores and the α-budget threshold τ (the ⌊αk⌋-th largest score, computed
by a cheap top-k outside), select and *compact* the routed documents'
token rows into a dense (capacity, D) buffer for the expensive parser —
no host round-trip, no full sort.

Selection rule (shared with ref.py and scheduler.plan_batch): rows with
score > τ are always selected — by definition of τ at most capacity−1
exist — while ties *at* τ consume a tie budget (capacity − |{s > τ}|)
first-come in row order. A strictly better row is therefore never
displaced by a tie, and host/device pick identical sets.

The rule's two exclusive prefix sums (tie rank, write position) are
O(N) XLA scans in the same jitted program (``ref.route_slots``): the
TPU's Pallas lowering has no cumsum, and a per-row scalar read of a
vector mask does not lower either. The kernel is the compaction: the
slot → source-row table rides in SMEM (scalar prefetch) and each
program DMAs ``block_n`` selected rows straight from HBM into its
output block, zeroing the empty slots, so the gather reads only the
⌊αN⌋ selected rows. Rows travel as (1, 1, D) slabs of an (N, 1, D)
view, so every DMA is a legal tile whatever the row index (a (1, D)
slice of a 2-D HBM array must start on an 8-row tile boundary), with D
padded up to whole 128-lane tiles (the reduced router's rows are 64
wide); the view costs XLA one relayout copy of the token matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.budget_route.ref import route_slots

LANES = 128


def _gather_kernel(idx_ref, tok_hbm, out_ref, sem, *, block_n: int):
    base = pl.program_id(0) * block_n

    def start(r, c):
        src = jnp.maximum(idx_ref[base + r], 0)
        pltpu.make_async_copy(tok_hbm.at[pl.ds(src, 1)],
                              out_ref.at[pl.ds(r, 1)], sem).start()
        return c

    def wait(r, c):
        # every copy is one (1, 1, D) row on the same semaphore
        pltpu.make_async_copy(tok_hbm.at[pl.ds(0, 1)],
                              out_ref.at[pl.ds(r, 1)], sem).wait()
        return c

    def clear(r, c):
        @pl.when(idx_ref[base + r] < 0)
        def _():
            out_ref[pl.ds(r, 1)] = jnp.zeros((1,) + out_ref.shape[1:],
                                             out_ref.dtype)
        return c

    jax.lax.fori_loop(0, block_n, start, 0)
    jax.lax.fori_loop(0, block_n, wait, 0)
    jax.lax.fori_loop(0, block_n, clear, 0)


@functools.partial(jax.jit, static_argnames=("capacity", "block_n",
                                             "interpret"))
def budget_route_kernel(scores, tokens, tau, *, capacity: int,
                        interpret: bool, block_n: int = 64):
    """scores (N,) f32; tokens (N, D); tau scalar threshold.

    Returns (routed (capacity, D), idx (capacity,) int32 source rows
    (-1 = empty), count scalar int32). ``block_n`` is the number of
    compacted rows each program gathers (the autotunable knob)."""
    n, d_tok = tokens.shape
    scores = scores.astype(jnp.float32)
    tau = jnp.asarray(tau, jnp.float32)
    idx, count = route_slots(scores, tau, capacity=capacity)
    block_n = max(1, min(int(block_n), capacity))
    cap_pad = -(-capacity // block_n) * block_n
    idx_pad = jnp.pad(idx, (0, cap_pad - capacity), constant_values=-1)
    d_pad = -(-d_tok // LANES) * LANES
    rows = jnp.pad(tokens.reshape(n, 1, d_tok),
                   ((0, 0), (0, 0), (0, d_pad - d_tok)))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block_n=block_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                        # slot -> row
            grid=(cap_pad // block_n,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # tokens (HBM)
            out_specs=pl.BlockSpec((block_n, 1, d_pad),
                                   lambda i, idx: (i, 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((cap_pad, 1, d_pad), tokens.dtype),
        interpret=interpret,
    )(idx_pad, rows)
    return out[:capacity, 0, :d_tok], idx, count
