"""Readings that the limit of ``logit_err`` is set from, in one process.

    python3 bench/calibrate.py --workloads <name>[,<name>...] \\
        --seeds <n>[,<n>...] --seconds <s>

For each cell and seed: the program's reading, from a short window of the
cell's own traffic that checks as many requests as a full run does, and the
control's, the plain reference at ``high`` precision (three bfloat16
passes) in the program's place on the same checked inputs. Prints one JSON
line per seed. Not part of a benchmark run: ``PERF.md`` records what it read
and the limits set from it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import catalog, compare, run, system, traffic  # noqa: E402


def readings(workload: str, seed: int, seconds: float,
             require_tpu: bool = True) -> dict:
    cell = catalog.load_cell(workload)
    cfg, tr = cell.config, cell.traffic
    weights, pool = run.make_inputs(cfg, tr, seed)
    replica = run.warm_replica(cfg, tr, weights(), pool, require_tpu)
    win = traffic.drive(replica, pool, tr, seed, seconds, run._span)
    del replica
    gc.collect()
    params = weights()
    block = cfg["reference_block"]
    program = compare.check_sample(cfg, params, pool, win.sample_idx,
                                   win.sample_logits, block)
    control_logits = compare.reference_logits(cfg, params, pool,
                                              win.sample_idx, block, "high")
    control = compare.check_sample(cfg, params, pool, win.sample_idx,
                                   control_logits, block)
    return dict(workload=workload, seed=seed, checked=int(len(program)),
                served=int(len(win.done)), program=float(program.max()),
                program_median=float(sorted(program)[len(program) // 2]),
                control=float(control.max()),
                control_median=float(sorted(control)[len(control) // 2]),
                limit=cfg["check"]["logit_err"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    system.require_program()
    run._devices(1, require_tpu=True)
    system.configure_cache()
    for workload in args.workloads.split(","):
        for seed in args.seeds.split(","):
            print(json.dumps(readings(workload, int(seed), args.seconds)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
