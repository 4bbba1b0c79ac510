"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --shape train_4k [--reduced] [--steps 100] [--ckpt-dir ckpts/qwen] \
        [--ckpt-every 50] [--mesh 1x1]

Production behaviors:
- restart-from-latest: on launch, restores the newest checkpoint in
  --ckpt-dir (params + optimizer state + data-pipeline step) and resumes;
- atomic async checkpoints every --ckpt-every steps (tmp+rename; training
  never blocks on I/O);
- straggler detection on step durations (logged; in multi-host deployment
  the detector's output feeds the elastic rescale planner);
- elastic restore: --mesh may differ from the mesh the checkpoint was
  written on; arrays are device_put into the new sharding.
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import numpy as np

from repro.checkpoint import checkpoint as ckpt_lib
from repro.device import ensure_compile_cache
from repro.distributed.fault import StragglerDetector
from repro.distributed.meshrules import AxisRules, use_rules
from repro.launch.mesh import make_mesh
from repro.launch.specs import build_cell


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny config for CPU runs")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="1x1",
                    help="e.g. 16x16 (data x model) or 2x16x16")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    ensure_compile_cache()

    dims = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("pod", "data", "model")[-len(dims):] if len(dims) == 3 \
        else ("data", "model")[-len(dims):]
    mesh = make_mesh(dims, axes)
    rules = AxisRules(mesh) if np.prod(dims) > 1 else None

    stop = {"now": False}
    signal.signal(signal.SIGTERM, lambda *_: stop.update(now=True))

    with mesh, use_rules(rules):
        cell = build_cell(args.arch, args.shape, rules=rules,
                          abstract=False, reduced=args.reduced)
        params, opt_state, _, batch0 = cell.args[:4]
        start_step = 0
        if args.ckpt_dir and ckpt_lib.latest_step(args.ckpt_dir) is not None:
            start_step, tree, meta = ckpt_lib.restore(args.ckpt_dir)
            params, opt_state = tree["params"], tree["opt_state"]
            print(f"[train] restored step {start_step} from {args.ckpt_dir}")
        jitted = jax.jit(cell.fn, donate_argnums=cell.donate_argnums)

        detector = StragglerDetector()
        losses = []
        pending = None
        for step in range(start_step, args.steps):
            if stop["now"]:
                print("[train] SIGTERM — checkpointing and exiting")
                break
            t0 = time.time()
            # re-synthesize the batch for this step (stateless pipeline)
            cell_b = build_cell(args.arch, args.shape, rules=rules,
                                abstract=False, reduced=args.reduced,
                                seed=step + 1)
            batch = cell_b.args[-1]
            params, opt_state, loss = jitted(
                params, opt_state, np.int32(step), batch)
            loss = float(loss)
            losses.append(loss)
            detector.record(0, time.time() - t0)
            if step % args.log_every == 0:
                print(f"[train] step {step} loss {loss:.4f} "
                      f"({time.time()-t0:.2f}s)", flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                if pending is not None:
                    pending.join()
                pending = ckpt_lib.save_async(
                    args.ckpt_dir, step + 1,
                    {"params": params, "opt_state": opt_state},
                    metadata={"arch": args.arch, "loss": loss})
        if pending is not None:
            pending.join()
        if args.ckpt_dir:
            ckpt_lib.save(args.ckpt_dir, args.steps,
                          {"params": params, "opt_state": opt_state},
                          metadata={"arch": args.arch,
                                    "loss": losses[-1] if losses else None})
        stragglers = detector.stragglers()
        print(f"[train] done; final loss "
              f"{losses[-1] if losses else float('nan'):.4f}; "
              f"stragglers={stragglers}")
        return losses


if __name__ == "__main__":
    main()
