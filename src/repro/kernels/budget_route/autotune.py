"""Block-size autotuning for the budget_route kernel.

Sweeps ``block_n`` (compacted rows gathered per program) candidates at
a given (N, D, capacity) shape through the shared ``autotune_common``
harness, and caches the winner per (shape, backend, device-mode) so
``budget_route`` picks it up transparently on later calls. The CI
sweep runs in interpret mode
(functional timing signal only — it exercises the grid/BlockSpec
plumbing at every candidate); the real-device sweep is gated behind
``device=True`` (CLI ``--device``) and refuses to run off-TPU, because
interpret-mode timings say nothing about TPU block residency. The
device flag is part of the cache/store key, so on a TPU host an
interpret sweep can never poison device dispatch.

With a persistent tuning store configured (``serve.py --tuning-dir``),
winners survive the process: a warm fleet restart re-dispatches at
tuned block sizes with zero re-sweeps.

CLI: ``python -m repro.kernels.budget_route.autotune [--route-64k]
[--device] [--tuning-dir DIR] [--json OUT]``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune_common, tuning_store
from repro.kernels.autotune_common import TuneRecord  # re-export
from repro.kernels.budget_route.kernel import budget_route_kernel

KERNEL_NAME = "budget_route"
DEFAULT_BLOCK_N = 64
# each program's output block is (block_n, 1, D): every value tiles;
# the cap keeps the double-buffered block (8 sublanes per row) in VMEM
DEFAULT_CANDIDATES = (8, 16, 32, 64, 128)
# the production routing shape (configs.py "adaparse-router" route_64k)
ROUTE_64K = (65536, 512)

__all__ = ["TuneRecord", "autotune_budget_route", "tuned_block_n",
           "ensure_tuned", "clear_cache", "DEFAULT_BLOCK_N",
           "DEFAULT_CANDIDATES", "ROUTE_64K", "KERNEL_NAME"]


def tuned_block_n(n: int, d_tok: int, capacity: int,
                  device: bool | None = None) -> int:
    """The cached/stored winner for this shape, or the default block
    size. ``device`` defaults to the mode dispatch actually runs in on
    this host (compiled on TPU, interpret elsewhere)."""
    return autotune_common.tuned_value(
        KERNEL_NAME, (n, d_tok, capacity), DEFAULT_BLOCK_N, device=device)


def clear_cache() -> None:
    autotune_common.clear_cache()


def _make_run(n: int, d_tok: int, capacity: int, device: bool, seed: int):
    rng = np.random.RandomState(seed)
    scores = jnp.asarray(rng.rand(n).astype(np.float32))
    tokens = jnp.asarray(rng.randint(0, 50000, (n, d_tok), dtype=np.int32))
    tau = jax.lax.top_k(scores, capacity)[0][-1]

    def make(block_n: int):
        def run():
            out, idx, count = budget_route_kernel(
                scores, tokens, tau, capacity=capacity, block_n=block_n,
                interpret=not device)
            jax.block_until_ready((out, idx, count))
        return run
    return make


def _clamp_candidates(candidates, n: int) -> tuple[int, ...]:
    # dedupe candidates after the kernel's block_n = min(block_n, n) clamp
    return tuple(sorted({min(int(c), n) for c in candidates}))


def autotune_budget_route(n: int, d_tok: int, capacity: int, *,
                          candidates: tuple[int, ...] = DEFAULT_CANDIDATES,
                          repeats: int = 2, device: bool = False,
                          seed: int = 0) -> TuneRecord:
    """Time every candidate block size at (n, d_tok, capacity), cache
    (and, when a tuning store is configured, persist) the winner.
    ``device=True`` compiles for the real accelerator and requires a
    TPU backend; otherwise the sweep runs in interpret mode."""
    if capacity < 1 or capacity > n:
        raise ValueError(f"capacity must be in [1, n={n}] (got {capacity})")
    return autotune_common.sweep(
        KERNEL_NAME, (n, d_tok, capacity), "block_n",
        _clamp_candidates(candidates, n),
        _make_run(n, d_tok, capacity, device, seed),
        repeats=repeats, device=device)


def ensure_tuned(n: int, d_tok: int, capacity: int, *,
                 candidates: tuple[int, ...] = DEFAULT_CANDIDATES,
                 repeats: int = 1, device: bool | None = None,
                 seed: int = 0) -> int:
    """Dispatch-time hook: the tuned winner, sweeping-and-persisting on
    a miss only when a tuning store is configured (else the default)."""
    if device is None:
        device = autotune_common.current_device_mode()
    return autotune_common.ensure_tuned(
        KERNEL_NAME, (n, d_tok, capacity), "block_n",
        _clamp_candidates(candidates, n),
        _make_run(n, d_tok, capacity, device, seed),
        DEFAULT_BLOCK_N, repeats=repeats, device=device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="budget_route block-size autotune sweep")
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d-tok", type=int, default=128)
    ap.add_argument("--alpha", type=float, default=0.05)
    ap.add_argument("--route-64k", action="store_true",
                    help="sweep the production route_64k shape "
                         f"{ROUTE_64K} instead of --n/--d-tok")
    ap.add_argument("--candidates", type=str, default=None,
                    help="comma-separated block_n candidates")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--device", action="store_true",
                    help="compile for the real accelerator (TPU only) "
                         "instead of the interpret-mode sweep")
    ap.add_argument("--tuning-dir", type=str, default=None,
                    help="persist the winner to this fleet-shared "
                         "tuning store")
    ap.add_argument("--json", type=str, default=None,
                    help="write the TuneRecord to this path")
    args = ap.parse_args(argv)
    if args.tuning_dir:
        tuning_store.configure(args.tuning_dir)
    n, d_tok = ROUTE_64K if args.route_64k else (args.n, args.d_tok)
    from repro.kernels.budget_route.ops import capacity_floor
    capacity = max(capacity_floor(args.alpha, n), 1)
    cands = DEFAULT_CANDIDATES
    if args.candidates:
        cands = tuple(int(c) for c in args.candidates.split(","))
    rec = autotune_budget_route(n, d_tok, capacity, candidates=cands,
                                repeats=args.repeats, device=args.device)
    print(f"budget_route autotune @ (n={n}, d={d_tok}, cap={capacity}) "
          f"[{rec.backend}{' device' if rec.device else ' interpret'}]")
    for block_n, t in rec.timings_s:
        tag = "  <-- winner" if block_n == rec.value else ""
        print(f"  block_n={block_n:<6d} {t * 1e3:8.2f} ms{tag}")
    if args.tuning_dir:
        tuning_store.get_store().flush()
        print(f"winner persisted to {args.tuning_dir}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dataclasses.asdict(rec), f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
