"""Shared by the chip benchmark's CPU tests: the harness on the path,
and its two cells cut to a size the CPU runs in seconds (a two-layer
float32 router, batches of 32, a 64-document pool of short pages)."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

TINY_ENCODER = dict(n_layers=2, d_model=32, n_heads=4, d_ff=64,
                    vocab_size=31090, max_len=64, param_dtype="float32",
                    compute_dtype="float32")
#: limits for the tiny float32 router, between the CPU's readings
#: (features 3e-8, accuracies 1.2e-7, BLEU 0) and the control's
#: (3.3e-3, 4.3e-2, 3.6e-4)
TINY_LIMITS = {"feature_gap": 1e-5, "pred_acc_gap": 1e-4,
               "probe_gap": 1e-5}


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


#: the cells rehearsed on the CPU: the benchmark's workload, and the ft
#: router over the same mix, which has no workload yet (PERF.md, open
#: questions) but shares every layer of the llm cell except route
CELLS = {"llm-bulk": "llm-bulk", "ft-bulk": "ft-router/bulk"}


def tiny_cell(cell: str):
    """(name, config, traffic, e2e, per_layer) of ``cell`` (a workload of
    BENCHMARK.json, or CONFIG/TRAFFIC) cut to the CPU's size."""
    workload = None if "/" in cell else cell
    name, config, traffic, e2e, per_layer = harness.resolve_cell(
        benchmark(), workload, cell if workload is None else None)
    config = dict(config, batch_size=32, fit_docs=64, alpha=0.1)
    config["limits"] = {k: TINY_LIMITS.get(k, v)
                        for k, v in config["limits"].items()}
    if "encoder" in config:
        config["encoder"] = dict(config["encoder"], **TINY_ENCODER)
    # pages of 72-120 tokens still fill the tiny encoder's 64 positions
    traffic = dict(traffic, pool_docs=64, probe_rate=1.0, page_tokens=96,
                   packed_widths=[1024, 2048])
    return name, config, traffic, e2e, per_layer
