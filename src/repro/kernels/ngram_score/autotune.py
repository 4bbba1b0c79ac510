"""Docs-per-program autotuning for the ngram_score kernel.

Same harness as budget_route's block_n sweep (``autotune_common``): time
``block_b`` candidates at a (B, max_len, max_n) probe shape, cache the
winner per (shape, backend, device-mode), persist it when a tuning
store is configured so a warm fleet restart re-dispatches without
re-sweeping. Interpret-mode timings are a functional signal only; the
real sweep is TPU-gated behind ``device=True``.

CLI: ``python -m repro.kernels.ngram_score.autotune [--device]
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
from repro.kernels.ngram_score.kernel import SUBLANES, ngram_bleu_kernel

KERNEL_NAME = "ngram_score"
DEFAULT_BLOCK_B = 8
# compiled blocks are (block_b, L): block_b must fill the 8 sublanes
DEFAULT_CANDIDATES = (8, 16, 32)

__all__ = ["TuneRecord", "autotune_ngram_bleu", "tuned_block_b",
           "ensure_tuned", "clear_cache", "DEFAULT_BLOCK_B",
           "DEFAULT_CANDIDATES", "KERNEL_NAME"]


def tuned_block_b(b: int, max_len: int, max_n: int = 4,
                  device: bool | None = None) -> int:
    """The cached/stored winner for this probe shape, or the default
    (eight documents per program)."""
    return autotune_common.tuned_value(
        KERNEL_NAME, (b, max_len, max_n), DEFAULT_BLOCK_B, device=device)


def clear_cache() -> None:
    autotune_common.clear_cache()


def _make_run(b: int, max_len: int, max_n: int, device: bool, seed: int):
    rng = np.random.RandomState(seed)
    ref = jnp.asarray(rng.randint(0, 5000, (b, max_len), dtype=np.int32))
    hyp = jnp.asarray(rng.randint(0, 5000, (b, max_len), dtype=np.int32))
    lr = jnp.asarray(rng.randint(1, max_len + 1, b, dtype=np.int32))
    lh = jnp.asarray(rng.randint(1, max_len + 1, b, dtype=np.int32))

    def make(block_b: int):
        def run():
            out = ngram_bleu_kernel(ref, hyp, lr, lh, max_len=max_len,
                                    max_n=max_n, interpret=not device,
                                    block_b=block_b)
            jax.block_until_ready(out)
        return run
    return make


def _clamp_candidates(candidates, b: int) -> tuple[int, ...]:
    # the kernel pads the batch to a block multiple: never clamp a
    # candidate below the batch rounded up to whole sublane groups
    b_up = -(-b // SUBLANES) * SUBLANES
    return tuple(sorted({max(1, min(int(c), b_up)) for c in candidates}))


def autotune_ngram_bleu(b: int, max_len: int, *, max_n: int = 4,
                        candidates: tuple[int, ...] = DEFAULT_CANDIDATES,
                        repeats: int = 2, device: bool = False,
                        seed: int = 0) -> TuneRecord:
    """Time every block_b candidate at (b, max_len, max_n), cache (and,
    with a tuning store configured, persist) the winner."""
    return autotune_common.sweep(
        KERNEL_NAME, (b, max_len, max_n), "block_b",
        _clamp_candidates(candidates, b),
        _make_run(b, max_len, max_n, device, seed),
        repeats=repeats, device=device)


def ensure_tuned(b: int, max_len: int, *, max_n: int = 4,
                 candidates: tuple[int, ...] = DEFAULT_CANDIDATES,
                 repeats: int = 1, device: bool | None = None,
                 seed: int = 0) -> int:
    """Dispatch-time hook: the tuned winner, sweeping-and-persisting on
    a miss only when a tuning store is configured (else the default)."""
    if device is None:
        device = autotune_common.current_device_mode()
    return autotune_common.ensure_tuned(
        KERNEL_NAME, (b, max_len, max_n), "block_b",
        _clamp_candidates(candidates, b),
        _make_run(b, max_len, max_n, device, seed),
        DEFAULT_BLOCK_B, repeats=repeats, device=device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="ngram_score docs-per-program autotune sweep")
    ap.add_argument("--b", type=int, default=256,
                    help="probe batch size (docs per score_batch call)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-n", type=int, default=4)
    ap.add_argument("--candidates", type=str, default=None,
                    help="comma-separated block_b candidates")
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
    rec = autotune_ngram_bleu(args.b, args.max_len, max_n=args.max_n,
                              candidates=cands, repeats=args.repeats,
                              device=args.device)
    print(f"ngram_score autotune @ (b={args.b}, max_len={args.max_len}, "
          f"max_n={args.max_n}) "
          f"[{rec.backend}{' device' if rec.device else ' interpret'}]")
    for block_b, t in rec.timings_s:
        tag = "  <-- winner" if block_b == rec.value else ""
        print(f"  block_b={block_b:<4d} {t * 1e3:8.2f} ms{tag}")
    if args.tuning_dir:
        tuning_store.get_store().flush()
        print(f"winner persisted to {args.tuning_dir}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dataclasses.asdict(rec), f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
