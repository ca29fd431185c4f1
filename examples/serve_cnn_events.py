"""End-to-end driver (the paper's kind: INFERENCE): event-driven CNN serving.

Serves image requests through the production serving tier (DESIGN.md §10):
a FIFO queue continuously batched into padded buckets, one AOT-warmed
executable per bucket, weights replicated over the (data, model) mesh.
Every completed request is checked against the dense oracle, per-layer
event stats feed the cost model, and throughput/energy are reported in
the paper's units (frames/s, frames/J).

    PYTHONPATH=src python examples/serve_cnn_events.py --rate 4 --ticks 4 \
        --size 64 --cache-dir /tmp/mnf_cache
"""
import argparse
import time

import jax
import numpy as np

from repro.costmodel import network_cycles, table4_row
from repro.data import cnn_batch
from repro.models.cnn import ALEXNET, VGG16, init_cnn_params, \
    make_cnn_pipeline, run_with_stats
from repro.serving import ServeEngine, ServeEngineConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", choices=("alexnet", "vgg16"), default="alexnet")
    ap.add_argument("--size", type=int, default=64)
    ap.add_argument("--rate", type=int, default=4,
                    help="request arrivals per serving tick")
    ap.add_argument("--ticks", type=int, default=4)
    ap.add_argument("--buckets", default="1,4,8",
                    help="compiled batch bucket sizes, ascending")
    ap.add_argument("--cache-dir", default=None,
                    help="executable snapshot directory (restarted "
                         "replicas re-warm from disk)")
    ap.add_argument("--weight-sparsity", type=float, default=0.5)
    ap.add_argument("--act-sparsity", type=float, default=0.6)
    args = ap.parse_args()

    spec = (ALEXNET if args.net == "alexnet" else VGG16).scaled(args.size)
    params = init_cnn_params(jax.random.PRNGKey(0), spec,
                             weight_sparsity=args.weight_sparsity)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    t0 = time.time()
    eng = ServeEngine(spec, params,
                      ServeEngineConfig(buckets=buckets,
                                        cache_dir=args.cache_dir))
    print(f"replica warmed in {time.time() - t0:.1f}s "
          f"(buckets {list(buckets)}, "
          f"compile {eng.warmup_s})")

    # Synthetic traffic, generated ahead of the serving loop; the dense
    # oracle (one compiled pipeline, DESIGN.md §5.1) checks every request.
    n_requests = args.rate * args.ticks
    frames = np.concatenate([
        np.asarray(cnn_batch(1, args.size, spec.in_ch, i,
                             activation_sparsity=args.act_sparsity))
        for i in range(n_requests)])
    ref_fn = make_cnn_pipeline(spec, mnf=False, donate=False)

    it = iter(frames)
    for _ in range(args.ticks):
        for _ in range(args.rate):
            eng.submit(next(it))
        eng.run_tick()
    stats = eng.stats()
    assert len(eng.completed) == n_requests, "queue did not drain"

    ref = np.asarray(ref_fn(params, frames))
    for i, req in enumerate(eng.completed):
        assert np.allclose(req.result, ref[req.rid], atol=5e-3, rtol=5e-3), \
            f"request {req.rid} diverged from the dense oracle"
        if i < args.rate:
            print(f"req {req.rid}: bucket {req.bucket} "
                  f"latency {req.latency_s * 1e3:.1f}ms "
                  f"pred={int(np.argmax(req.result))}")

    # price one frame's measured event stream on the paper's accelerator
    _, layer_stats = run_with_stats(params, frames[:1], spec)
    row = table4_row(layer_stats, w_density=1 - args.weight_sparsity)
    cyc = network_cycles(layer_stats, "mnf", d_w=1 - args.weight_sparsity)
    dense_macs = sum(s["dense_macs"] for s in layer_stats)
    event_macs = sum(s["event_macs"] for s in layer_stats)
    print(f"\nserved {stats['requests']} frames at "
          f"{stats['requests_s']:.1f} req/s "
          f"(p50 {stats['p50_ms']:.1f}ms, p99 {stats['p99_ms']:.1f}ms, "
          f"{stats['recompiles']} compiles, all at warmup)")
    print(f"event/dense MAC ratio: {event_macs / dense_macs:.3f}")
    print(f"modeled on MNF ASIC (Table 3 hw): {row['frames_s']:.1f} frames/s,"
          f" {row['power_mw']:.1f} mW, {row['frames_j']:.1f} frames/J "
          f"({cyc:,.0f} cycles/frame)")


if __name__ == "__main__":
    main()
