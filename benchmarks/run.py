"""The paper benchmarks: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run           # all, small settings
  PYTHONPATH=src python -m benchmarks.run --only bench_sync
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks import (bench_accel, bench_balance, bench_cost_ratio,
                            bench_isolation, bench_pipeline,
                            bench_scalability, bench_sync)

    suites = {
        "bench_accel": lambda: bench_accel.run(small=True),        # Fig. 8
        "bench_scalability": bench_scalability.run,                # Fig. 9
        "bench_pipeline": bench_pipeline.run,                      # Fig. 10/15
        "bench_sync": bench_sync.run,                              # Fig. 11
        "bench_balance": bench_balance.run,                        # Fig. 12
        "bench_isolation": bench_isolation.run,                    # Fig. 13
        "bench_cost_ratio": bench_cost_ratio.run,                  # Fig. 14
    }
    failures = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        try:
            result = fn()
            print(f"    ok in {time.time() - t0:.1f}s")
            _summarize(name, result)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print("FAILED:", failures)
        sys.exit(1)
    print("\nall benchmarks complete; results under results/benchmarks/")


def _summarize(name, result):
    if name == "bench_accel":
        for alg, r in result.items():
            # skip the non-algorithm entries (_meta, fault_recovery)
            if isinstance(r, dict) and "speedup_vectorized" in r:
                print(f"    {alg}: {r['speedup_vectorized']:.1f}x accel")
                # direct indexing: a dropped kernel×model cell must
                # KeyError loudly here, never silently skip the ratio
                mx = r["sharded_matrix"]["per_iter_s"]
                models = r["sharded_matrix"]["models"]
                ratios = " ".join(
                    f"{m}={mx[f'pallas/{m}'] / mx[f'reference/{m}']:.2f}x"
                    for m in models)
                print(f"    {alg}: pallas/reference per-iter {ratios}")
        fr = result.get("fault_recovery")
        if fr:
            print(f"    fault-recovery: {fr['devices_before']}→"
                  f"{fr['devices_after']} devices, "
                  f"migration {fr['migration_s']*1e3:.0f}ms, "
                  f"bit-identical={fr['state_bit_identical']}")
    elif name == "bench_sync":
        for ds, r in result.items():
            print(f"    {ds}: skip={r['skip_fraction']:.0%} "
                  f"volume-reduction={r['sync_volume_reduction']:.1f}x")
    elif name == "bench_pipeline":
        f = result["fig15"]
        print(f"    s_opt: measured={f['argmin_measured']} "
              f"lemma1={f['s_opt_lemma1']}")
    elif name == "bench_balance":
        c1 = result["case1"]
        print(f"    case1 balanced/optimum = "
              f"{c1['balanced_makespan_s'] / c1['theoretical_optimum_s']:.3f}")
    elif name == "bench_isolation":
        print(f"    isolation speedup = {result['isolation_speedup']:.1f}x")
    elif name == "bench_cost_ratio":
        for alg, rows in result.items():
            trend = " ".join(f"{ns}:{r['middleware_ratio']:.0%}"
                             for ns, r in rows.items())
            print(f"    {alg}: {trend}")


if __name__ == "__main__":
    main()
