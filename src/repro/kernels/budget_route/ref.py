"""Pure-jnp oracle for budget_route: stable select-and-compact.

Selection rule (shared with the Pallas kernel and scheduler.plan_batch):
rows with score > τ are always kept (at most capacity−1 exist when τ is
the capacity-th largest score); ties at τ fill the remaining slots in
row order. A strictly better row is therefore never displaced by a tie.

``route_slots`` is that rule as compaction slots; the kernel computes
its slots with it too (XLA scans outside the kernel) and only the
compact-gather of the token rows differs between the two paths.
"""
from __future__ import annotations

import jax.numpy as jnp


def route_slots(scores, tau, *, capacity: int):
    """Scores (N,) -> (idx (capacity,) int32 source row of each
    compacted slot, -1 past ``count``; count scalar int32)."""
    n = scores.shape[0]
    gt = scores > tau
    eq = scores == tau
    tie_cap = capacity - jnp.sum(gt)
    eq_i = eq.astype(jnp.int32)
    tie_rank = jnp.cumsum(eq_i) - eq_i
    mask = gt | (eq & (tie_rank < tie_cap))
    mask_i = mask.astype(jnp.int32)
    pos = jnp.cumsum(mask_i) - mask_i
    keep = mask & (pos < capacity)
    rows = jnp.arange(n, dtype=jnp.int32)
    idx = jnp.full((capacity,), -1, jnp.int32).at[
        jnp.where(keep, pos, capacity)].set(rows, mode="drop")
    count = jnp.minimum(jnp.sum(mask_i), capacity).astype(jnp.int32)
    return idx, count


def budget_route_ref(scores, tokens, tau, *, capacity: int):
    idx, count = route_slots(scores, tau, capacity=capacity)
    out = jnp.where((idx >= 0)[:, None], tokens[jnp.maximum(idx, 0)], 0)
    return out.astype(tokens.dtype), idx, count
