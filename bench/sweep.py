"""Find the highest open-loop rate a replica sustains, in one process.

    python3 bench/sweep.py --workload <name> --rates <r>[,<r>...] \\
        --seconds <s> --seed <n>

Serves the cell's traffic at each rate in turn, with the rate in the file
replaced, and prints one JSON line per rate: latency percentiles from the
due time, the first and second half's p95 (a backlog that grows shows as a
second half far above the first), the mean batch and how long the queue
took to drain after the last arrival. Run once when an open-loop cell is
defined; the cell's rate is then fixed in its traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from bench import catalog, run, system, traffic  # noqa: E402


def at_rate(replica, pool, tr: dict, rate: float, seed: int,
            seconds: float) -> dict:
    win = traffic.drive(replica, pool, dict(tr, rate=rate), seed, seconds,
                        run._span)
    lat = win.latency_s * 1e3
    half = win.due < win.start + seconds / 2
    return dict(rate=rate, requests=int(len(lat)),
                p50_ms=float(np.percentile(lat, 50)),
                p95_ms=float(np.percentile(lat, 95)),
                p99_ms=float(np.percentile(lat, 99)),
                p95_first_half_ms=float(np.percentile(lat[half], 95)),
                p95_second_half_ms=float(np.percentile(lat[~half], 95)),
                lateness_p95_ms=float(np.percentile(win.lateness_s, 95) * 1e3),
                mean_batch=float(np.mean(win.tick_sizes)),
                drain_s=float(win.done.max() - win.end))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = catalog.load_cell(args.workload)
    system.require_program()
    run._devices(cell.chips, require_tpu=True)
    system.configure_cache()
    weights, pool = run.make_inputs(cell.config, cell.traffic, args.seed)
    replica = run.warm_replica(cell.config, cell.traffic, weights(), pool,
                               require_tpu=True)
    for rate in args.rates.split(","):
        print(json.dumps(at_rate(replica, pool, cell.traffic, float(rate),
                                 args.seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
