"""Online graph-query serving driver (DESIGN.md §5).

Loads one graph onto the host mesh and replays a seeded open-loop
workload of k-hop / shortest-path / personalized-PageRank / lookup
queries through the serving stack — admission queue, batched
multi-source execution, result LRU — optionally killing a device
mid-replay to exercise the elastic shrink(+grow) path under live
traffic:

  PYTHONPATH=src python -m repro.launch.graph_serve \
      --num-vertices 2000 --num-edges 16000 --requests 100 --rate 200

  # elastic: kill device 3 during the 3rd fused iteration, recover it
  # ten iterations later — serving continues across both migrations
  PYTHONPATH=src python -m repro.launch.graph_serve --kill-at 3 \
      --kill-device 3 --recover-at 13
"""
from __future__ import annotations

import argparse
import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

from repro.dist.fault import FailureSchedule, FleetMonitor  # noqa: E402
from repro.graph import generate  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.serve import (GraphServeRouter, GraphServeSession,  # noqa: E402
                         generate_workload, replay)


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-vertices", type=int, default=2_000)
    ap.add_argument("--num-edges", type=int, default=16_000)
    ap.add_argument("--graph-seed", type=int, default=7)
    ap.add_argument("--num-shards", type=int, default=8)
    ap.add_argument("--kernel", choices=("reference", "pallas"),
                    default="reference")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait", type=float, default=0.005,
                    help="admission deadline (virtual seconds)")
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="offered load, requests per virtual second")
    ap.add_argument("--workload-seed", type=int, default=0)
    ap.add_argument("--repeat-fraction", type=float, default=0.2,
                    help="fraction of requests re-issuing an earlier "
                         "query (cache-hit path)")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="kill a device at this fused iteration of the "
                         "next run — serving migrates and continues")
    ap.add_argument("--kill-device", type=int, default=3)
    ap.add_argument("--recover-at", type=int, default=None,
                    help="bring the killed device back at this "
                         "iteration — the mesh grows again")
    args = ap.parse_args(argv)

    g = generate.rmat(args.num_vertices, args.num_edges,
                      seed=args.graph_seed)
    failures = None
    monitor = None
    if args.kill_at is not None:
        recov = ([(args.recover_at, args.kill_device)]
                 if args.recover_at is not None else ())
        failures = FailureSchedule(
            kills=[(args.kill_at, args.kill_device)], recoveries=recov)
        monitor = FleetMonitor(num_hosts=args.num_shards)
    session = GraphServeSession(
        g, num_shards=args.num_shards, kernel=args.kernel,
        max_batch=args.max_batch, monitor=monitor, failures=failures)
    router = GraphServeRouter(session, max_wait=args.max_wait)

    wl = generate_workload(
        num_requests=args.requests, num_vertices=g.num_vertices,
        rate=args.rate, seed=args.workload_seed,
        repeat_fraction=args.repeat_fraction)
    answers, stats = replay(router, wl)

    print(f"graph |V|={g.num_vertices} |E|={g.num_edges}, "
          f"{args.num_shards} shards, kernel={args.kernel}")
    print(f"{stats['completed']} completed ({stats['cached']} cache hits) "
          f"in {stats['wall_s']:.2f}s wall — "
          f"{stats['throughput_qps']:.1f} qps, "
          f"p50 {stats['p50_ms']:.2f}ms p99 {stats['p99_ms']:.2f}ms")
    for kind, row in stats["kinds"].items():
        print(f"  {kind:8s} n={row['count']:4d} cached={row['cached']:3d} "
              f"p50={row['p50_ms']:8.2f}ms p99={row['p99_ms']:8.2f}ms "
              f"mean_batch={row['mean_batch']:.1f}")
    print(f"families compiled: {len(session.compiled_families)}, "
          f"mesh epoch: {session.mesh_epoch}, "
          f"cache: {router.cache.stats.as_dict()}")
    return stats


if __name__ == "__main__":
    main()
