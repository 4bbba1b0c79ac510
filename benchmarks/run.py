"""Benchmark aggregator — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines and writes the engine
hot-path metrics to ``BENCH_engine.json`` and the stress-scenario
sweep to ``BENCH_scenarios.json`` (machine-readable, one file per run)
so the perf trajectory is tracked across PRs.

  python -m benchmarks.run [--fast] [--engine-only] [--scenarios-only] \
      [--engine-json BENCH_engine.json] \
      [--scenarios-json BENCH_scenarios.json] \
      [--history-jsonl BENCH_history.jsonl]

Every run also *appends* its key metrics + the git sha to
``BENCH_history.jsonl`` (one JSON object per line), so the per-commit
perf trajectory accumulates across PRs instead of each run overwriting
the last snapshot.
"""
import argparse
import json
import subprocess
import sys
import time


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:
        return "unknown"


def _append_history(args, bench: str, metrics: dict) -> None:
    """One JSONL line per bench run: key metrics + provenance."""
    if not args.history_jsonl:
        return
    line = {"bench": bench, "git_sha": _git_sha(),
            "unix_time": time.time(), "fast": bool(args.fast),
            "metrics": metrics}
    with open(args.history_jsonl, "a") as f:
        f.write(json.dumps(line, sort_keys=True) + "\n")
    print(f"{bench} history appended -> {args.history_jsonl}",
          file=sys.stderr)


def _write_scenarios(args, t0: float) -> None:
    """Run the stress-scenario sweep and persist BENCH_scenarios.json
    (every named scenario asserts the byte-identical-records invariant
    against its single-node reference before its counters land here)."""
    from benchmarks import bench_scenarios

    metrics = bench_scenarios.run(fast=args.fast)
    if args.scenarios_json:
        payload = {"bench": "scenarios", "fast": bool(args.fast),
                   "unix_time": time.time(), "metrics": metrics}
        with open(args.scenarios_json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"scenario metrics -> {args.scenarios_json}",
              file=sys.stderr)
    _append_history(args, "scenarios", metrics)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller corpora / fewer steps")
    ap.add_argument("--engine-only", action="store_true",
                    help="only the engine hot-path bench (the one that "
                         "feeds BENCH_engine.json; what CI runs)")
    ap.add_argument("--engine-json", default="BENCH_engine.json",
                    help="where to write the engine metrics "
                         "(empty string disables)")
    ap.add_argument("--scenarios-only", action="store_true",
                    help="only the stress-scenario sweep (the one that "
                         "feeds BENCH_scenarios.json; what CI runs)")
    ap.add_argument("--scenarios-json", default="BENCH_scenarios.json",
                    help="where to write the per-scenario stress "
                         "counters (empty string disables)")
    ap.add_argument("--history-jsonl", default="BENCH_history.jsonl",
                    help="append-only per-run history: one JSON line "
                         "with the run's key metrics + git sha "
                         "(empty string disables)")
    args = ap.parse_args()
    from repro.device import ensure_compile_cache
    ensure_compile_cache()
    n = 120 if args.fast else 240
    t0 = time.time()
    print("name,us_per_call,derived")

    from benchmarks import (bench_engine, bench_kernels,
                            bench_parser_quality, bench_roofline,
                            bench_scaling, bench_selection_models)
    if args.scenarios_only:
        _write_scenarios(args, t0)
        print(f"total_wall_s,{(time.time()-t0)*1e6:.0f},"
              f"{time.time()-t0:.1f}s", file=sys.stderr)
        return
    engine_metrics = bench_engine.run(n_docs=max(n, 160), batch_size=128,
                                      repeats=1 if args.fast else 3)
    if args.engine_json:
        payload = {"bench": "engine", "fast": bool(args.fast),
                   "unix_time": time.time(), "metrics": engine_metrics}
        with open(args.engine_json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"engine metrics -> {args.engine_json}", file=sys.stderr)
    _append_history(args, "engine", engine_metrics)
    if args.engine_only:
        print(f"total_wall_s,{(time.time()-t0)*1e6:.0f},"
              f"{time.time()-t0:.1f}s", file=sys.stderr)
        return
    bench_scaling.run(n_docs=max(n // 2, 80))
    bench_parser_quality.run(n_docs=n)
    bench_selection_models.run(n_docs=max(n, 160),
                               sft_steps=60 if args.fast else 120,
                               dpo_steps=30 if args.fast else 50)
    bench_kernels.run()
    bench_roofline.run()
    _write_scenarios(args, t0)
    print(f"total_wall_s,{(time.time()-t0)*1e6:.0f},"
          f"{time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == '__main__':
    main()
