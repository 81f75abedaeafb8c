"""Reads a cell's control: the plain reference in bfloat16, put in the
program's place and judged by the cell's own check.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--runs 4]

For each seed it builds the cell's graph and traffic at the cell's own
size, computes the answers of the window's first ``--runs`` runs with
the bfloat16 reference, and prints one JSON line with each number the
check compares beside its limit.  The control has to fail: a cell whose
control passes its check has a limit that cannot tell float32 from
bfloat16.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--runs", type=int, default=4)
    args = ap.parse_args(argv)

    from repro.graph.structure import Graph

    from bench import graphs, harness, traffic

    _, _, config, params = harness.load_cell(args.workload)
    graph = Graph(*graphs.make(config))
    for seed in args.seeds:
        t = time.perf_counter()
        work = traffic.make(params, graph, seed)
        checks, failed = work.control(args.runs, params["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "runs": args.runs, "failed": failed,
                          "seconds": time.perf_counter() - t,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
