"""Share of the roofline of one aggregation iteration, in %.

The work is counted from the graph and the algorithm, not from the
program's tiles, so it reads the same whatever implements an iteration:
one 4-byte source id per edge, the vertex state (N x K x 4 bytes) read
once and written once, and the 4-byte out-degree vector.  The least time
is the larger of bytes over peak HBM bandwidth and operations (a divide
and an add per edge, a multiply and an add per vertex) over peak FLOP/s.
It is divided by the device's busy time per iteration in the trace.

Exact only where every edge is active in every iteration (PageRank):
elsewhere it reads nothing.
"""

from bench import tracing


def iteration_bytes(num_vertices: int, num_edges: int,
                    state_width: int) -> int:
    return 4 * num_edges + 2 * 4 * num_vertices * state_width \
        + 4 * num_vertices


def iteration_flops(num_vertices: int, num_edges: int) -> int:
    return 2 * num_edges + 2 * num_vertices


def read(record):
    if record.trace is None or record.traffic["algorithm"] != "pagerank":
        return None
    busy = tracing.busy_s(record.trace)
    if busy <= 0.0:
        return None
    n, e = record.num_vertices, record.num_edges
    least = max(iteration_bytes(n, e, record.state_width)
                / record.peaks["hbm_bytes_per_s"],
                iteration_flops(n, e) / record.peaks["flops_per_s"])
    return 100.0 * least / (busy / sum(record.iterations))
