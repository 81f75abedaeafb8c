"""Bring-up check: the graph middleware's fused device path on a TPU.

Drives the main path once through the entry points a user calls, on a
Graph500 graph (R-MAT a=.57 b=.19 c=.19, edgefactor 16, SCALE 22 by
default: 4,194,304 vertices, 67,108,864 edges) generated from ``--seed``:

* ``analytics`` — PageRank and Bellman-Ford SSSP through
  ``plug.Middleware(daemon=get_daemon("sharded", kernel="pallas",
  csr_config=<pinned Pallas config>), upper="mesh", model="bsp")``;
* ``serve`` — a ``serve.GraphServeRouter`` over a
  ``GraphServeSession(kernel="pallas")`` on the same graph answers a few
  sssp and khop queries.

Every result is checked against ``plug.run_reference`` (per seed, for
the queries).  The run also asserts that the fused ``DriveLoop`` ran and
that its compiled step holds the Pallas kernel (``tpu_custom_call``).

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # only the four-chip mesh path

``--chips 4`` runs the analytics phase alone, with ``num_shards=4`` over
a four-device mesh, and prints where the stacked shards live.  Each line
before the last is one JSON record; a passing run ends with
``{"ok": true, "device": {...}}``.  Without a TPU, or on any mismatch,
the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

#: Graph500 R-MAT initiator and edgefactor (graph500.org specification)
RMAT = {"a": 0.57, "b": 0.19, "c": 0.19}
EDGEFACTOR = 16
#: the pinned Pallas configuration: tile kernel, one-hot MXU merge,
#: per-edge gather ahead of the kernel, 512-edge tiles (no autotuning,
#: so no XLA twin can stand in for the kernel)
KERNEL_CONFIG = {"edge_tile": 512, "lowering": "pallas", "merge": "onehot",
                 "gather": "take"}
PAGERANK_RTOL = 1e-4  # sum merge: f32 order of summation differs


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def shard_devices(mw) -> dict:
    """Device ids holding each stacked CSR tensor's shards."""
    csr = mw.daemon.stacked["csr"]
    return {k: sorted({s.device.id for s in a.addressable_shards})
            for k, a in csr.items()}


def compare(name: str, got: np.ndarray, want: np.ndarray) -> dict:
    if name == "pagerank":
        ok = bool(np.allclose(got, want, rtol=PAGERANK_RTOL, atol=0.0))
    else:  # min monoid: bit-identical to the reference
        ok = bool(np.array_equal(got, want))
    err = np.abs(got.astype(np.float64) - want)
    return {"correct": ok, "max_abs_err": float(err.max()),
            "max_rel_err": float((err / np.maximum(np.abs(want), 1e-30)
                                  ).max())}


def analytics(g, parts, cfg, dev, num_shards: int, *, placement: bool):
    """PageRank and SSSP through the fused sharded Pallas path."""
    from repro import plug
    from repro.graph.algorithms import pagerank, sssp_bf

    results = []
    for name, prog in (("pagerank", pagerank(g)), ("sssp_bf", sssp_bf(g))):
        t0 = time.perf_counter()
        mw = plug.Middleware(
            g, prog, daemon=plug.get_daemon("sharded", kernel="pallas",
                                            csr_config=cfg),
            upper="mesh", model="bsp", partitions=parts)
        build_s = time.perf_counter() - t0
        if mw._fused_kind != "bsp":
            raise RuntimeError(f"{name}: fused DriveLoop not selected "
                               f"({mw._fused_kind!r})")
        t0 = time.perf_counter()
        compiled = mw.compile_step()
        compile_s = time.perf_counter() - t0
        if "tpu_custom_call" not in compiled.as_text():
            raise RuntimeError(f"{name}: compiled step holds no Pallas "
                               "kernel (tpu_custom_call)")
        res = mw.run()
        rec = {"phase": "analytics", "algorithm": name,
               "num_shards": num_shards, "mesh_devices": mw.daemon.m,
               "kernel_config": mw.daemon._csr_config.label,
               "csr_tiles": int(mw.daemon.stacked["csr"]["seg"].shape[1]),
               "build_s": build_s, "compile_s": compile_s,
               "iterations": res.iterations, "converged": res.converged,
               "wall_s": res.wall_time}
        if placement:
            rec["shard_devices"] = shard_devices(mw)
        del mw, compiled
        gc.collect()
        t0 = time.perf_counter()
        want, ref_it = plug.run_reference(g, prog)
        rec.update(reference_s=time.perf_counter() - t0,
                   reference_iterations=ref_it,
                   peak_bytes_in_use=peak_bytes(dev),
                   **compare(name, res.state, want))
        log(**rec)
        results.append(rec["correct"])
    return results


def serving(g, cfg, dev, seed: int, num_queries: int = 4):
    """A few sssp and khop queries through the router, each checked
    against a single-seed reference run."""
    from repro import plug, serve
    from repro.graph.algorithms import BATCHED_QUERIES, INF

    rng = np.random.default_rng(seed)
    # seeds drawn per edge: sources with out-edges, hub-biased like
    # skewed query traffic
    seeds = [int(s) for s in g.src[rng.integers(0, g.num_edges,
                                                2 * num_queries)]]
    queries = ([serve.Query.make("sssp", s) for s in seeds[:num_queries]]
               + [serve.Query.make("khop", s, hops=2)
                  for s in seeds[num_queries:]])
    t0 = time.perf_counter()
    session = serve.GraphServeSession(g, num_shards=1, kernel="pallas",
                                      csr_config=cfg,
                                      max_batch=num_queries)
    router = serve.GraphServeRouter(session)
    tickets = [router.submit(q)[0] for q in queries]
    router.drain()
    serve_s = time.perf_counter() - t0
    answers = router.take_results()
    del router, session
    gc.collect()
    results = []
    for q, t in zip(queries, tickets):
        prog = BATCHED_QUERIES[q.kind](g, [q.seeds], **dict(q.params))
        want = plug.run_reference(g, prog)[0][:, 0]
        got = answers[t].value
        ok = bool(np.array_equal(got, want))
        log(phase="serve", kind=q.kind, seeds=list(q.seeds),
            params=dict(q.params), batch=answers[t].batch,
            iterations=answers[t].iterations,
            reached=int((want < INF).sum()), correct=ok)
        results.append(ok)
    log(phase="serve", queries=len(queries), serve_s=serve_s,
        peak_bytes_in_use=peak_bytes(dev), correct=all(results))
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=22,
                    help="Graph500 SCALE: 2**scale vertices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.graph import generate
    from repro.graph.partition import partition_contiguous
    from repro.kernels.autotune import CSRConfig
    from repro.launch.cache import use_compile_cache

    cache_dir = use_compile_cache()
    cfg = CSRConfig(**KERNEL_CONFIG)
    num_shards = args.chips
    n = 1 << args.scale
    t0 = time.perf_counter()
    g = generate.rmat_stream(n, EDGEFACTOR * n, seed=args.seed, **RMAT)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    parts = partition_contiguous(g, num_shards)
    log(phase="setup", platform=dev.platform, device_kind=dev.device_kind,
        device_count=len(devices), chips=args.chips, scale=args.scale,
        num_vertices=g.num_vertices, num_edges=g.num_edges, seed=args.seed,
        generate_s=gen_s, partition_s=time.perf_counter() - t0,
        compile_cache=cache_dir)

    t_all = time.perf_counter()
    results = analytics(g, parts, cfg, dev, num_shards,
                        placement=args.chips > 1)
    if args.chips == 1:
        results += serving(g, cfg, dev, args.seed)
    log(phase="done", seconds=time.perf_counter() - t_all,
        peak_bytes_in_use=peak_bytes(dev), checks=len(results),
        correct=all(results))
    if not all(results):
        print("chip_smoke: results differ from the reference",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
