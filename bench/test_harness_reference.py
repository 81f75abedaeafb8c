"""The benchmark's generators and plain references, against independent
loop versions and against the program's own generators."""
import heapq

import numpy as np
import pytest

from bench import graphs, reference


def _tuples(seed):
    return graphs.rmat_tuples(10, 16, a=0.57, b=0.19, c=0.19, seed=seed)


def _graph500(seed):
    return graphs.graph500(10, 16, a=0.57, b=0.19, c=0.19, seed=seed)


def test_generators_copy_the_programs():
    from repro.graph import generate

    n, src, dst = _tuples(2**31 + 9)
    g = generate.rmat_stream(n, 16 * n, seed=2**31 + 9)
    np.testing.assert_array_equal(src, g.src)
    np.testing.assert_array_equal(dst, g.dst)


def test_rmat_does_not_depend_on_the_threads(monkeypatch):
    a = _graph500(3)
    monkeypatch.setattr(graphs, "RMAT_THREADS", 1)
    for x, y in zip(a, _graph500(3)):
        np.testing.assert_array_equal(x, y)


def test_graph500_permutes_shuffles_and_mirrors():
    n, t_src, t_dst = _tuples(7)
    m = t_src.size
    n2, src, dst, w = _graph500(7)
    assert n2 == n and src.size == dst.size == w.size == 2 * m
    # undirected: the second half of the arcs is the first, reversed
    np.testing.assert_array_equal(src[m:], dst[:m])
    np.testing.assert_array_equal(dst[m:], src[:m])
    np.testing.assert_array_equal(w[m:], w[:m])
    assert w.dtype == np.float32 and w.min() >= 0.0 and w.max() < 1.0
    # the labels are a permutation: the same tuples up to relabelling
    # and order, and the hubs no longer sit at the low ids
    hub = np.bincount(t_src, minlength=n).argmax()
    assert hub == 0
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    t_deg = np.bincount(t_src, minlength=n) + np.bincount(t_dst, minlength=n)
    np.testing.assert_array_equal(np.sort(deg), np.sort(2 * t_deg))
    assert deg.argmax() != 0
    assert not np.array_equal(src[:m], t_src)


def test_graph500_is_a_function_of_the_graph_seed():
    config = {"generator": "graph500", "scale": 6, "edgefactor": 16,
              "graph_seed": 0, "initiator": {"a": 0.57, "b": 0.19,
                                             "c": 0.19}}
    for x, y in zip(graphs.make(config), graphs.make(config)):
        np.testing.assert_array_equal(x, y)
    other = graphs.make(dict(config, graph_seed=1))
    assert not np.array_equal(other[1], graphs.make(config)[1])


def _graph(seed=0, n=200, e=1500):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    w = rng.uniform(1.0, 10.0, e).astype(np.float32)
    return n, src, dst, w


def test_pagerank_matches_a_loop():
    n, src, dst, _ = _graph()
    deg = np.bincount(src, minlength=n)
    r = [1.0 / n] * n
    for _ in range(5):
        acc = [0.0] * n
        for s, d in zip(src, dst):
            acc[d] += r[s] / max(deg[s], 1)
        r = [0.15 / n + 0.85 * a for a in acc]
    got = reference.pagerank(n, src, dst, damping=0.85, iterations=5)
    np.testing.assert_allclose(got, r, rtol=1e-12)


def _dijkstra(n, src, dst, w, s):
    """float32 path sums, added in path order."""
    adj = [[] for _ in range(n)]
    for a, b, c in zip(src, dst, w):
        adj[a].append((b, c))
    dist = np.full(n, reference.F32_MAX, np.float32)
    dist[s] = 0.0
    heap = [(np.float32(0.0), s)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for v, c in adj[u]:
            nd = np.float32(d + c)
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    return dist


@pytest.mark.parametrize("threads", [1, 3])
def test_shortest_paths_match_dijkstra(monkeypatch, threads):
    monkeypatch.setattr(reference, "THREADS", threads)
    n, src, dst, w = _graph(seed=1, e=900)
    sp = reference.ShortestPaths(n, src, dst, w)
    roots = [0, 5, 17, 17]
    dist, it = sp.run(roots)
    for k, s in enumerate(roots):
        np.testing.assert_array_equal(dist[:, k], _dijkstra(n, src, dst, w,
                                                            s))
    assert it >= 2


def test_shortest_paths_count_the_quiet_iteration():
    # a path 0 -> 1 -> 2: distances settle in iteration 2, and
    # iteration 3 is the first in which none falls
    sp = reference.ShortestPaths(3, np.array([0, 1], np.int32),
                                 np.array([1, 2], np.int32),
                                 np.array([1.0, 2.0], np.float32))
    dist, it = sp.run([0])
    assert dist[:, 0].tolist() == [0.0, 1.0, 3.0] and it == 3


def test_bf16_controls_differ_from_the_references():
    n, src, dst, w = _graph(seed=2)
    want = reference.pagerank(n, src, dst, damping=0.85, iterations=10)
    got = reference.pagerank(n, src, dst, damping=0.85, iterations=10,
                             bf16=True)
    assert reference.rank_rel_err(got, want) > 1e-3
    sp = reference.ShortestPaths(n, src, dst, w)
    assert reference.dist_mismatch(sp.run([3], bf16=True)[0],
                                   sp.run([3])[0]) > 0
