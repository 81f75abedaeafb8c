"""Plain references of the benchmark's algorithms, written from their
definitions with numpy alone, and the comparisons that decide
``correct``.

Each reference can also run in bfloat16 (``bf16=True``): every stored
vertex value and every message rounded to bfloat16, sums accumulated in
float32.  That is the control: the step below the float32 the
configurations state, which a faster program might be tempted to take.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

F32_MAX = np.float32(np.finfo(np.float32).max)
#: threads relaxing slices of the edge list (numpy releases the
#: interpreter lock in the gathers and reductions)
THREADS = 8


def _bf16(x: np.ndarray) -> np.ndarray:
    """Rounds float32 values to the nearest bfloat16, kept as float32."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def pagerank(n: int, src: np.ndarray, dst: np.ndarray, *, damping: float,
             iterations: int, bf16: bool = False) -> np.ndarray:
    """``iterations`` power steps ``r' = (1-d)/n + d * sum_{u->v} r_u /
    outdeg(u)`` from ``r = 1/n``, in float64 (or the bfloat16 control).
    Parallel edges count once each; a vertex with no out-edge sends
    nothing (its mass is not redistributed)."""
    deg = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float64)
    r = np.full(n, 1.0 / n)
    if bf16:
        r = _bf16(r).astype(np.float64)
    for _ in range(iterations):
        msg = r / deg
        if bf16:
            msg = _bf16(msg).astype(np.float64)
        agg = np.bincount(dst, weights=msg[src], minlength=n)
        if bf16:
            agg = agg.astype(np.float32).astype(np.float64)
        r = (1.0 - damping) / n + damping * agg
        if bf16:
            r = _bf16(r).astype(np.float64)
    return r


class ShortestPaths:
    """Bellman-Ford from several sources at once, in float32.

    Each iteration relaxes the out-edges of the vertices whose distance
    fell in the previous iteration (all vertices in the first), reading
    the distances as they stood at the start of the iteration; the run
    ends with the first iteration in which no distance falls, and that
    iteration is counted.  Distances are sums of float32 weights along a
    path, added in path order, so the fixed point is exact and does not
    depend on the order of relaxation.  Unreached vertices keep the
    largest finite float32.
    """

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray,
                 w: np.ndarray):
        # one value sort of ``dst << 32 | index``: a stable argsort
        order = np.sort((dst.astype(np.int64) << 32)
                        | np.arange(dst.size, dtype=np.int64)) & 0xFFFFFFFF
        self.n = n
        self.src = src[order]
        self.dst = dst[order]
        self.w = w[order].astype(np.float32)

    def run(self, sources, *, bf16: bool = False):
        """Returns ``(dist (n, K) float32, iterations)``."""
        k = len(sources)
        dist = np.full((self.n, k), F32_MAX, np.float32)
        dist[np.asarray(sources), np.arange(k)] = 0.0
        active = np.ones(self.n, dtype=bool)
        it = 0
        with ThreadPoolExecutor(THREADS) as pool:
            while True:
                it += 1
                e = np.flatnonzero(active[self.src])
                new = dist.copy()
                # edges are sorted by destination, so each slice of them
                # relaxes on its own; a destination split between two
                # slices takes the smaller of their minima below
                parts = np.array_split(e, max(1, min(THREADS,
                                                     e.size // 65536)))
                for tgt, best in pool.map(
                        lambda part: self._relax(dist, part, bf16), parts):
                    new[tgt] = np.minimum(new[tgt], best)
                fell = (new < dist).any(axis=1)
                dist = new
                if not fell.any():
                    return dist, it
                active = fell

    def _relax(self, dist, e, bf16):
        """Per-destination minimum of ``dist[src] + w`` over edges ``e``."""
        if e.size == 0:
            return np.empty(0, np.int64), np.empty((0, dist.shape[1]),
                                                   np.float32)
        d = self.dst[e]
        cand = dist[self.src[e]] + self.w[e][:, None]
        if bf16:
            # the largest float32 rounds to bfloat16's inf, which the
            # minimum never takes: an unreached vertex stays unreached
            cand = _bf16(cand)
        starts = np.flatnonzero(np.r_[True, d[1:] != d[:-1]])
        return d[starts], np.minimum.reduceat(cand, starts, axis=0)


def rank_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest relative gap of a rank from the float64 reference (every
    reference rank is at least ``(1-d)/n > 0``)."""
    got = np.asarray(got, np.float64).reshape(-1)
    return float(np.max(np.abs(got - want) / want))


def dist_mismatch(got: np.ndarray, want: np.ndarray) -> int:
    """Distances that are not bit for bit the reference's."""
    return int(np.count_nonzero(np.asarray(got, np.float32) != want))
