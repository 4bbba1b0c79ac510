"""Run one cell of the chip benchmark and print its result line.

    python benchmarks/chip/cell.py --workload llm-bulk --seed 7 \
        --seconds 40 --trace 0

From the root of a checkout on a machine with a TPU. One process, one
run: refuse any host whose JAX default device is not a TPU (there is no
CPU fallback), set up and warm up the cell, drive the engine's batch
loop for ``--seconds``, compare a seeded sample of what the window
emitted with the plain reference, then print one JSON line:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones, read
under the profiler, with the program's own spans and counters on for
the window), ``device``, with ``--trace 1`` ``breakdown`` (with
``idle_by_span``: the device's idle seconds by the program span that
held it up), and last ``checks``: each compared number beside its
limit. The same numbers are the last lines on standard error.

A configuration that names a ``parser_model`` brings its module under
``configs/``, which builds the parser's weights, hands the engine the
program's backend for it, and adds its own numbers to ``checks``; the
hooks are listed in ``harness.py``.

``--workload`` names an entry of ``BENCHMARK.json``; ``--cell
CONFIG/TRAFFIC`` runs a configuration and a traffic mix that have no
entry yet. ``--keep-trace DIR`` copies the profiler's trace there.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    who = ap.add_mutually_exclusive_group(required=True)
    who.add_argument("--workload", help="a workload of BENCHMARK.json")
    who.add_argument("--cell", help="CONFIG/TRAFFIC, by file name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=pathlib.Path, default=None)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    # libtpu logs under a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve_cell(bench, args.workload, args.cell)
    chips = next((w["chips"] for w in bench["workloads"]
                  if w["name"] == args.workload), 1)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"cell: JAX's default device is {devices[0].platform!r} "
              f"x{len(devices)}; this cell needs {chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    result = harness.run_cell(*cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              keep_trace=args.keep_trace)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} {value} limit {limit}", file=sys.stderr)
    print(f"check correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # JAX's persistent compile cache lives inside the checkout, at a
    # fixed path, whatever directory the host's environment names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.exit(main())
