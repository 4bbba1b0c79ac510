"""Comparison-block autotuning for the fast_features kernel.

The kernel's distinct-token scan extracts each comparison column from a
``block_l``-lane slice of the stream; the sweep times power-of-two
candidates (multiples of the 128 lanes, so every one tiles) at a packed
(width, max_len) shape through the shared ``autotune_common`` harness
and persists the winner when a tuning store is configured. Because
``pack_routing_batch`` quantizes widths to powers of two, a fleet sees
only O(log) distinct shapes — the first worker to meet one sweeps, the
rest (and every warm restart) read the store.

CLI: ``python -m repro.kernels.fast_features.autotune [--device]
[--tuning-dir DIR] [--json OUT]``.
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
from repro.kernels.fast_features.kernel import fast_features_kernel

KERNEL_NAME = "fast_features"
DEFAULT_BLOCK_L = 128
DEFAULT_CANDIDATES = (128, 256, 512)
SWEEP_DOCS = 64                        # synthetic batch the sweep times

__all__ = ["TuneRecord", "autotune_fast_features", "tuned_block_l",
           "ensure_tuned", "clear_cache", "DEFAULT_BLOCK_L",
           "DEFAULT_CANDIDATES", "KERNEL_NAME"]


def tuned_block_l(width: int, max_len: int,
                  device: bool | None = None) -> int:
    """The cached/stored winner for this packed shape, or the default."""
    return autotune_common.tuned_value(
        KERNEL_NAME, (width, max_len), DEFAULT_BLOCK_L, device=device)


def clear_cache() -> None:
    autotune_common.clear_cache()


def _make_run(width: int, max_len: int, device: bool, seed: int):
    rng = np.random.RandomState(seed)
    # worst-case occupancy: every stream runs the full width
    tok = jnp.asarray(rng.randint(0, 10000, (SWEEP_DOCS, width),
                                  dtype=np.int32))
    full = jnp.full((SWEEP_DOCS,), width, jnp.int32)
    first = jnp.asarray(rng.randint(0, width + 1, SWEEP_DOCS,
                                    dtype=np.int32))
    pages = jnp.full((SWEEP_DOCS,), 4, jnp.int32)
    empty = jnp.zeros((SWEEP_DOCS,), jnp.int32)

    def make(block_l: int):
        def run():
            out = fast_features_kernel(
                tok, full, first, pages, empty, max_len=max_len,
                block_l=block_l, ws=2, scramble=3, mangled=4,
                latex_lo=8010, ident_lo=8510, interpret=not device)
            jax.block_until_ready([o for o in out if o is not None])
        return run
    return make


def _clamp_candidates(candidates, width: int) -> tuple[int, ...]:
    # the kernel needs block_l | width; widths are powers of two >= 128,
    # so power-of-two candidates clamped to the width always divide it
    return tuple(sorted({min(int(c), width) for c in candidates}))


def autotune_fast_features(width: int, max_len: int = 0, *,
                           candidates: tuple[int, ...] = DEFAULT_CANDIDATES,
                           repeats: int = 2, device: bool = False,
                           seed: int = 0) -> TuneRecord:
    """Time every block_l candidate at (width, max_len), cache (and,
    with a tuning store configured, persist) the winner."""
    return autotune_common.sweep(
        KERNEL_NAME, (width, max_len), "block_l",
        _clamp_candidates(candidates, width),
        _make_run(width, max_len, device, seed),
        repeats=repeats, device=device)


def ensure_tuned(width: int, max_len: int = 0, *,
                 candidates: tuple[int, ...] = DEFAULT_CANDIDATES,
                 repeats: int = 1, device: bool | None = None,
                 seed: int = 0) -> int:
    """Dispatch-time hook: the tuned winner, sweeping-and-persisting on
    a miss only when a tuning store is configured (else the default)."""
    if device is None:
        device = autotune_common.current_device_mode()
    return autotune_common.ensure_tuned(
        KERNEL_NAME, (width, max_len), "block_l",
        _clamp_candidates(candidates, width),
        _make_run(width, max_len, device, seed),
        DEFAULT_BLOCK_L, repeats=repeats, device=device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fast_features comparison-block autotune sweep")
    ap.add_argument("--width", type=int, default=2048,
                    help="packed stream width (power of two)")
    ap.add_argument("--max-len", type=int, default=64,
                    help="encoder token window (0: features only)")
    ap.add_argument("--candidates", type=str, default=None,
                    help="comma-separated block_l candidates")
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
    cands = DEFAULT_CANDIDATES
    if args.candidates:
        cands = tuple(int(c) for c in args.candidates.split(","))
    rec = autotune_fast_features(args.width, args.max_len,
                                 candidates=cands, repeats=args.repeats,
                                 device=args.device)
    print(f"fast_features autotune @ (width={args.width}, "
          f"max_len={args.max_len}) "
          f"[{rec.backend}{' device' if rec.device else ' interpret'}]")
    for block_l, t in rec.timings_s:
        tag = "  <-- winner" if block_l == rec.value else ""
        print(f"  block_l={block_l:<6d} {t * 1e3:8.2f} ms{tag}")
    if args.tuning_dir:
        tuning_store.get_store().flush()
        print(f"winner persisted to {args.tuning_dir}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dataclasses.asdict(rec), f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
