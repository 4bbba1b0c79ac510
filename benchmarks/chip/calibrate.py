"""Readings that a cell's correctness limits are set from.

    python benchmarks/chip/calibrate.py --workload llm-bulk \
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 5 \
        --out calibrate-llm-bulk.json

On the chip, in one process: for each of ``--seeds``, one run of the
cell as the benchmark makes it (a ``--seconds`` window at the cell's
own sizes) and its compared numbers, the lower readings; for each of
``--control-seeds``, the control's numbers, the upper readings: the
reference one precision below the configuration's, put in the
program's place, on the cell's own batches. The benchmark's runs never
run the control. Prints and writes every reading as JSON.
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


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The control's numbers for one seed."""
    import check
    import harness
    import traffic as T
    from repro.data.synthetic import Document

    stages = harness.fit_stages(config, traffic, seed)
    encoder = weights = None
    if config["variant"] == "llm":
        encoder = harness.load_module(
            BENCH / "configs" / f"{config['reference']}.py")
        weights = harness.seeded_weights(config, encoder, seed, stages)
    ref = check.Reference(config, traffic, T.stream_seed(seed, 4),
                          T.stream_seed(seed, 5), stages, weights, encoder,
                          harness.parser_model(config, seed))
    pool = T.make_pool(traffic, traffic["corpus"], seed, Document)
    return check.control_numbers(
        ref, T.batches(pool, config["batch_size"], seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", type=pathlib.Path, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve_cell(bench, args.workload, None)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    out = {"workload": args.workload, "program": {}, "control": {}}
    for s in filter(None, args.seeds.split(",")):
        t = time.perf_counter()
        res = harness.run_cell(*cell, seed=int(s), seconds=args.seconds,
                               trace=False, t_start=t)
        out["program"][s] = {k: v for k, (v, _) in res["checks"].items()}
        out["program"][s]["correct"] = res["correct"]
        print(json.dumps({"seed": s, "program": out["program"][s],
                          "run_s": time.perf_counter() - t}), flush=True)
    for s in filter(None, args.control_seeds.split(",")):
        t = time.perf_counter()
        out["control"][s] = control(cell[1], cell[2], int(s))
        print(json.dumps({"seed": s, "control": out["control"][s],
                          "run_s": time.perf_counter() - t}), flush=True)
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    # JAX's persistent compile cache lives inside the checkout, at a
    # fixed path, whatever directory the host's environment names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.exit(main())
