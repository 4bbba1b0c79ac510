"""The paper's quality tables on the synthetic corpus, one module each.
Prints ``name,us_per_call,derived`` CSV lines.

  python -m benchmarks.run [--fast]

These are CPU reproductions of the paper's accuracy tables, not speed
measurements; the chip benchmark is ``benchmarks/chip/cell.py``.
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller corpora / fewer steps")
    args = ap.parse_args()
    from repro.device import ensure_compile_cache
    ensure_compile_cache()
    n = 120 if args.fast else 240
    t0 = time.time()
    print("name,us_per_call,derived")

    from benchmarks import bench_parser_quality, bench_selection_models
    bench_parser_quality.run(n_docs=n)
    bench_selection_models.run(n_docs=max(n, 160),
                               sft_steps=60 if args.fast else 120,
                               dpo_steps=30 if args.fast else 50)
    print(f"total_wall_s,{(time.time()-t0)*1e6:.0f},"
          f"{time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == '__main__':
    main()
