"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocks import build_blocks
from repro.graph import generate
from repro.graph.algorithms import label_prop, pagerank, sssp_bf
from repro.graph.partition import partition_contiguous
from repro.kernels import ops, ref


def _finite_allclose(a, b, atol, rtol=1e-4):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_array_equal(np.isfinite(a), np.isfinite(b))
    np.testing.assert_allclose(np.where(np.isfinite(a), a, 0),
                               np.where(np.isfinite(b), b, 0),
                               atol=atol, rtol=rtol)


# --------------------------------------------------------------------------
# edge_block
# --------------------------------------------------------------------------
@pytest.mark.parametrize("algf", [pagerank, sssp_bf, label_prop])
@pytest.mark.parametrize("block_size", [64, 128, 333])
def test_edge_block_sweep(algf, block_size):
    g = generate.rmat(300, 2500, seed=13)
    prog = algf(g)
    part = partition_contiguous(g, 1)[0]
    bs = build_blocks(part, block_size)
    state, aux = prog.init(g)
    args = [jnp.asarray(x) for x in (state, aux, bs.vids, bs.lsrc, bs.ldst,
                                     bs.weights, bs.emask)]
    p_ref, c_ref = ref.edge_block_aggregate(*args, program=prog)
    p_pal, c_pal = ops.edge_block_aggregate(*args, program=prog, impl="pallas")
    _finite_allclose(p_ref, p_pal, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_ref), np.asarray(c_pal))


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 4, 4, 128, 32),     # MHA
    (2, 8, 2, 256, 64),     # GQA 4:1
    (2, 6, 1, 192, 64),     # MQA, non-pow2 seq blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, hq, hkv, s, d, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    o_ref = ref.flash_attention(q, k, v, causal=causal)
    o_pal = ops.flash_attention(q, k, v, causal=causal, impl="pallas",
                                block_q=64, block_k=64)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32), atol=atol)


def test_flash_attention_block_shapes():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 512, 64))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 512, 64))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 512, 64))
    o_ref = ref.flash_attention(q, k, v, causal=True)
    for bq, bk in [(64, 128), (128, 64), (256, 256), (512, 512)]:
        o = ops.flash_attention(q, k, v, causal=True, impl="pallas",
                                block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


# --------------------------------------------------------------------------
# SSD scan (Mamba2)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 8, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (2, 96, 4, 16, 4, 8, 32),   # groups == heads/1, chunk not dividing? 96%32=0
])
def test_ssd_scan_sweep(b, s, h, p, g, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = 0.5 * jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(0.3 * jax.random.normal(ks[2], (h,)))
    bm = 0.3 * jax.random.normal(ks[3], (b, s, g, n))
    cm = 0.3 * jax.random.normal(ks[4], (b, s, g, n))
    y_seq = ref.ssd_scan_reference(x, dt, a, bm, cm)
    y_chk = ref.ssd_scan_chunked_ref(x, dt, a, bm, cm, chunk=chunk)
    y_pal = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, impl="pallas")
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq), atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_seq), atol=2e-4)


def test_ssd_final_state_matches_sequential():
    """return_final_state must equal the state of the naive recurrence —
    the prefill → decode handoff depends on it."""
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    b, s, h, p, g, n = 2, 64, 2, 16, 1, 8
    x = 0.5 * jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(0.3 * jax.random.normal(ks[2], (h,)))
    bm = 0.3 * jax.random.normal(ks[3], (b, s, g, n))
    cm = 0.3 * jax.random.normal(ks[4], (b, s, g, n))
    _, state = ref.ssd_scan_chunked_ref(x, dt, a, bm, cm, chunk=16,
                                        return_final_state=True)
    # sequential recurrence
    bh = jnp.repeat(bm, h // g, axis=2)
    hstate = jnp.zeros((b, h, n, p))
    for t in range(s):
        decay = jnp.exp(a[None] * dt[:, t])
        hstate = hstate * decay[..., None, None] + (
            (dt[:, t, :, None] * bh[:, t])[..., :, None] * x[:, t][..., None, :])
    np.testing.assert_allclose(np.asarray(state), np.asarray(hstate), atol=2e-4)


# --------------------------------------------------------------------------
# CSR tile kernel battery: compaction invariants, differential tests over
# the full autotune space (pallas ≡ XLA twin ≡ flat ≡ naive numpy oracle),
# adversarial graphs, frontier filtering, autotune cache
# --------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.template import MIN, MAX, OR, SUM, Monoid, VertexProgram
from repro.graph.compaction import (build_csr_tiles, pad_tileset,
                                    tiles_from_blockset)
from repro.kernels.autotune import (AutotuneCache, CSRConfig, DEFAULT_SPACE,
                                    autotune_csr)

N_V = 24  # deliberately not a multiple of 8: exercises RT/ST rounding

# small tiles force multi-tile layouts + hub splitting on tiny graphs;
# one config per (lowering, merge, gather) family of the tuning space
TEST_SPACE = (
    CSRConfig(edge_tile=32, merge="flat"),
    CSRConfig(edge_tile=32, merge="sorted", gather="take"),
    CSRConfig(edge_tile=32, merge="onehot", gather="onehot"),
    CSRConfig(edge_tile=32, lowering="pallas", merge="onehot",
              gather="take"),
    CSRConfig(edge_tile=32, lowering="pallas", merge="onehot",
              gather="onehot"),
)

_GEN = {
    "sum": lambda s, d, w, a: s * w + a,   # exercises the aux gather
    "min": lambda s, d, w, a: s + w,
    "max": lambda s, d, w, a: s * w,
    "or": lambda s, d, w, a: s,            # indicator pass-through
}


def _program(monoid: Monoid, k: int = 2) -> VertexProgram:
    return VertexProgram(
        name=f"csr_test_{monoid.name}", state_width=k, aux_width=1,
        monoid=monoid, msg_gen=_GEN[monoid.name],
        msg_apply=lambda *a: (_ for _ in ()).throw(AssertionError),
        init=lambda g: None)


def _state_for(monoid: Monoid, k: int = 2, seed: int = 0):
    rng = np.random.default_rng(seed)
    if monoid.name == "or":
        state = (rng.random((N_V, k)) < 0.5).astype(np.float32)
    else:
        state = rng.uniform(0.5, 8.0, (N_V, k)).astype(np.float32)
    aux = rng.uniform(0.0, 2.0, (N_V, 1)).astype(np.float32)
    return state, aux


def _edges_from_pairs(pairs):
    """Edge arrays from a hypothesis-drawn list of (src, dst) pairs, with
    deterministic positive weights."""
    src = np.asarray([p[0] for p in pairs], np.int32)
    dst = np.asarray([p[1] for p in pairs], np.int32)
    w = (1.0 + (src.astype(np.float32) * 3 + dst) % 5).astype(np.float32)
    return src, dst, w


def _oracle(prog, state, aux, src, dst, w, active=None):
    """Per-edge numpy scatter — the naive daemon's math, identity at
    message-free vertices.  Bit-identical ground truth for the selection
    monoids (min/max/or), merge-order truth for sum."""
    monoid = prog.monoid
    if active is not None and src.size:
        keep = np.asarray(active)[src]
        src, dst, w = src[keep], dst[keep], w[keep]
    agg = np.full((N_V, prog.state_width), monoid.identity, np.float32)
    cnt = np.zeros(N_V, np.int64)
    if src.size:
        msgs = np.asarray(prog.msg_gen(
            jnp.asarray(state[src]), jnp.asarray(state[dst]),
            jnp.asarray(w[:, None]), jnp.asarray(aux[src])))
        monoid.scatter_at(agg, dst, msgs)
        np.add.at(cnt, dst, 1)
    agg = np.where((cnt > 0)[:, None], agg,
                   np.float32(monoid.identity)).astype(np.float32)
    return agg, cnt.astype(np.int32)


def _run_cfg(cfg, prog, state, aux, src, dst, w, active=None):
    """One tuning-space point, run eagerly (tiny adversarial shapes —
    avoids a jit recompile per drawn example)."""
    ts = build_csr_tiles(src, dst, w, N_V, edge_tile=cfg.edge_tile,
                         hub_threshold=cfg.hub_threshold)
    csr = {k: jnp.asarray(v) for k, v in ts.arrays().items()}
    if active is not None:
        csr["emask"] = csr["emask"] & jnp.asarray(active)[csr["gsrc"]]
    agg, cnt, _ = ops.csr_aggregate(
        jnp.asarray(state), jnp.asarray(aux), csr, program=prog,
        num_vertices=N_V, config=cfg)
    return np.asarray(agg), np.asarray(cnt)


def _assert_variants_match(monoid, src, dst, w, active=None, seed=0):
    prog = _program(monoid)
    state, aux = _state_for(monoid, seed=seed)
    agg0, cnt0 = _oracle(prog, state, aux, src, dst, w, active=active)
    for cfg in TEST_SPACE:
        agg, cnt = _run_cfg(cfg, prog, state, aux, src, dst, w,
                            active=active)
        np.testing.assert_array_equal(cnt, cnt0, err_msg=cfg.label)
        if monoid.idempotent:
            # selections: bit-identical under ANY tiling/order/duplication
            np.testing.assert_array_equal(agg, agg0, err_msg=cfg.label)
        else:
            np.testing.assert_allclose(agg, agg0, rtol=1e-5, atol=1e-5,
                                       err_msg=cfg.label)


_pairs = st.lists(st.tuples(st.integers(0, N_V - 1),
                            st.integers(0, N_V - 1)),
                  min_size=0, max_size=120)


# -- compaction invariants --------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(_pairs)
def test_csr_tiles_pack_every_edge_exactly_once(pairs):
    """Every input edge lands in exactly one live tile slot, with its
    weight; padded slots are dead (emask False, ids 0)."""
    src, dst, w = _edges_from_pairs(pairs)
    ts = build_csr_tiles(src, dst, w, N_V, edge_tile=16)
    live = ts.emask
    got = sorted(zip(ts.gsrc[live].tolist(), ts.gdst[live].tolist(),
                     ts.w[:, :, 0][live].tolist()))
    want = sorted(zip(src.tolist(), dst.tolist(), w.tolist()))
    assert got == want
    assert ts.num_edges == src.size
    # dead slots follow the padding convention
    assert not ts.gsrc[~live].any() and not ts.gdst[~live].any()
    assert not ts.w[:, :, 0][~live].any()


@settings(max_examples=20, deadline=None)
@given(_pairs)
def test_csr_tile_local_indices_roundtrip(pairs):
    """Tile-local indirection is consistent: svids[lsrc] recovers gsrc,
    rows[seg] recovers gdst, and seg is sorted within each tile (the
    sorted-segment-merge precondition)."""
    src, dst, w = _edges_from_pairs(pairs)
    ts = build_csr_tiles(src, dst, w, N_V, edge_tile=16)
    for t in range(ts.num_tiles):
        live = ts.emask[t]
        np.testing.assert_array_equal(ts.svids[t][ts.lsrc[t][live]],
                                      ts.gsrc[t][live])
        np.testing.assert_array_equal(ts.rows[t][ts.seg[t][live]],
                                      ts.gdst[t][live])
        seg = ts.seg[t][live]
        assert (np.diff(seg) >= 0).all()  # sorted segments


def test_csr_low_degree_rows_never_span_tiles():
    """Degree bucketing: with every in-degree ≤ hub_threshold, each dst
    row lives entirely inside one tile (per-tile merges are final)."""
    g = generate.rmat(200, 1200, seed=3)
    et = 128
    deg = np.bincount(g.dst, minlength=g.num_vertices)
    assert deg.max() <= et  # precondition: no hubs at this scale
    ts = build_csr_tiles(g.src, g.dst, None, g.num_vertices, edge_tile=et)
    assert ts.hub_rows().size == 0
    owner: dict = {}
    for t in range(ts.num_tiles):
        for r in np.unique(ts.gdst[t][ts.emask[t]]):
            assert owner.setdefault(int(r), t) == t
    assert ts.padding_ratio < 0.5


def test_csr_hub_rows_split_across_tiles_and_combine_exactly():
    """A single giant-degree hub (3.5× the edge tile) streams across
    dedicated tiles; the cross-tile segmented combine finishes it to the
    same aggregate the oracle computes — bit-identically for min."""
    et = 32
    hub_deg = int(3.5 * et)
    rng = np.random.default_rng(7)
    src = rng.integers(0, N_V, hub_deg + 40).astype(np.int32)
    dst = np.concatenate([np.full(hub_deg, 5, np.int32),
                          rng.integers(0, N_V, 40).astype(np.int32)])
    w = rng.uniform(0.5, 2.0, src.size).astype(np.float32)
    ts = build_csr_tiles(src, dst, w, N_V, edge_tile=et)
    assert 5 in ts.hub_rows().tolist()
    _assert_variants_match(MIN, src, dst, w)
    _assert_variants_match(SUM, src, dst, w)


def test_csr_empty_edge_list():
    """E = 0 still yields a well-formed (single dead tile) layout and an
    all-identity aggregate with zero counts."""
    src = np.empty(0, np.int32)
    dst = np.empty(0, np.int32)
    w = np.empty(0, np.float32)
    ts = build_csr_tiles(src, dst, w, N_V, edge_tile=16)
    assert ts.num_tiles == 1 and not ts.emask.any()
    for monoid in (MIN, MAX, SUM, OR):
        prog = _program(monoid)
        state, aux = _state_for(monoid)
        for cfg in TEST_SPACE:
            agg, cnt = _run_cfg(cfg, prog, state, aux, src, dst, w)
            assert (agg == np.float32(monoid.identity)).all(), cfg.label
            assert not cnt.any(), cfg.label


def _loop_csr_tiles(src, dst, w, eblock, et, hub):
    """Per-tile loop form of ``build_csr_tiles`` (before vectorization):
    cut the dst-sorted order tile by tile, then ``np.unique`` per tile."""
    order = np.argsort(dst, kind="stable")
    dst_s = dst[order]
    tiles, cur = [], []
    for row in np.split(np.arange(dst.size),
                        np.flatnonzero(np.diff(dst_s)) + 1):
        if row.size > hub:  # hub row: stream-fill across tiles
            for i in row:
                cur.append(i)
                if len(cur) == et:
                    tiles.append(cur)
                    cur = []
        else:  # low-degree row: cut early, never straddle
            if cur and len(cur) + row.size > et:
                tiles.append(cur)
                cur = []
            cur.extend(row)
            if len(cur) == et:
                tiles.append(cur)
                cur = []
    if cur or not tiles:
        tiles.append(cur)
    nt = len(tiles)
    out = {"seg": np.zeros((nt, et), np.int32),
           "lsrc": np.zeros((nt, et), np.int32),
           "w": np.zeros((nt, et, 1), np.float32),
           "emask": np.zeros((nt, et), bool),
           "gsrc": np.zeros((nt, et), np.int32),
           "gdst": np.zeros((nt, et), np.int32),
           "eblock": np.full((nt, et), -1, np.int32)}
    uniq = []
    for t, idx in enumerate(tiles):
        ed = order[np.asarray(idx, np.int64)]
        ne = ed.size
        urows, out["seg"][t, :ne] = np.unique(dst[ed], return_inverse=True)
        usrc, out["lsrc"][t, :ne] = np.unique(src[ed], return_inverse=True)
        uniq.append((urows, usrc))
        out["w"][t, :ne, 0] = w[ed]
        out["emask"][t, :ne] = True
        out["gsrc"][t, :ne] = src[ed]
        out["gdst"][t, :ne] = dst[ed]
        out["eblock"][t, :ne] = eblock[ed]
    rt = -(-max(1, *(u[0].size for u in uniq)) // 8) * 8
    st = -(-max(1, *(u[1].size for u in uniq)) // 8) * 8
    out["rows"] = np.zeros((nt, rt), np.int32)
    out["svids"] = np.zeros((nt, st), np.int32)
    for t, (urows, usrc) in enumerate(uniq):
        out["rows"][t, :urows.size] = urows
        out["svids"][t, :usrc.size] = usrc
    return out


@pytest.mark.parametrize("et,hub", [(8, None), (16, 3), (32, 16), (512, None)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_csr_tiles_match_per_tile_loop(seed, et, hub):
    """The vectorized compaction builds the same tiles, bit for bit, as
    the per-tile loop it replaced — hubs, empty graphs and all."""
    from repro.graph.structure import stable_argsort

    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 60))
    e = [0, 150, 400][seed]
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    dst[: e // 3] = dst[0] if e else 0  # one hub row
    w = rng.random(e).astype(np.float32)
    eblock = rng.integers(0, 9, e).astype(np.int32)
    np.testing.assert_array_equal(stable_argsort(dst),
                                  np.argsort(dst, kind="stable"))
    ts = build_csr_tiles(src, dst, w, n, edge_tile=et, hub_threshold=hub,
                         eblock=eblock)
    want = _loop_csr_tiles(src, dst, w, eblock, et,
                           ts.hub_threshold)
    assert ts.num_tiles == want["seg"].shape[0]
    for name, a in want.items():
        got = getattr(ts, name)
        assert got.dtype == a.dtype, name
        np.testing.assert_array_equal(got, a, err_msg=name)


def test_pad_tileset_preserves_aggregate_bit_for_bit():
    """Padding a tile set to a bigger (nt, RT, ST) envelope (the sharded
    daemon's rectangular stacking) must not change any variant's output."""
    g = generate.rmat(N_V, 160, seed=11)
    prog = _program(MIN)
    state, aux = _state_for(MIN)
    for cfg in TEST_SPACE:
        ts = build_csr_tiles(g.src, g.dst, g.weights, N_V,
                             edge_tile=cfg.edge_tile)
        padded = pad_tileset(ts, num_tiles=ts.num_tiles + 3,
                             row_tile=ts.row_tile + 8,
                             src_tile=ts.src_tile + 16)
        outs = []
        for t in (ts, padded):
            csr = {k: jnp.asarray(v) for k, v in t.arrays().items()}
            agg, cnt, _ = ops.csr_aggregate(
                jnp.asarray(state), jnp.asarray(aux), csr, program=prog,
                num_vertices=N_V, config=cfg)
            outs.append((np.asarray(agg), np.asarray(cnt)))
        np.testing.assert_array_equal(outs[0][0], outs[1][0],
                                      err_msg=cfg.label)
        np.testing.assert_array_equal(outs[0][1], outs[1][1],
                                      err_msg=cfg.label)


def test_pad_tileset_rejects_shrinking():
    g = generate.rmat(N_V, 80, seed=2)
    ts = build_csr_tiles(g.src, g.dst, None, N_V, edge_tile=16)
    with pytest.raises(ValueError, match="smaller"):
        pad_tileset(ts, num_tiles=ts.num_tiles - 1, row_tile=ts.row_tile,
                    src_tile=ts.src_tile)


# -- differential property tests over the tuning space ----------------------
@settings(max_examples=12, deadline=None)
@given(_pairs)
def test_csr_variants_match_oracle_min(pairs):
    _assert_variants_match(MIN, *_edges_from_pairs(pairs))


@settings(max_examples=12, deadline=None)
@given(_pairs)
def test_csr_variants_match_oracle_max(pairs):
    _assert_variants_match(MAX, *_edges_from_pairs(pairs))


@settings(max_examples=12, deadline=None)
@given(_pairs)
def test_csr_variants_match_oracle_or(pairs):
    _assert_variants_match(OR, *_edges_from_pairs(pairs))


@settings(max_examples=12, deadline=None)
@given(_pairs)
def test_csr_variants_match_oracle_sum(pairs):
    _assert_variants_match(SUM, *_edges_from_pairs(pairs))


def test_csr_sum_bit_exact_on_integer_messages():
    """Integer-valued states/weights make sum exact in f32 at this scale:
    every variant must then agree with the oracle bit for bit, not just
    to tolerance — merge order can no longer hide a wrong edge."""
    rng = np.random.default_rng(4)
    src = rng.integers(0, N_V, 300).astype(np.int32)
    dst = rng.integers(0, N_V, 300).astype(np.int32)
    w = rng.integers(1, 4, 300).astype(np.float32)
    prog = _program(SUM)
    state = rng.integers(0, 8, (N_V, 2)).astype(np.float32)
    aux = rng.integers(0, 4, (N_V, 1)).astype(np.float32)
    agg0, cnt0 = _oracle(prog, state, aux, src, dst, w)
    for cfg in TEST_SPACE:
        agg, cnt = _run_cfg(cfg, prog, state, aux, src, dst, w)
        np.testing.assert_array_equal(agg, agg0, err_msg=cfg.label)
        np.testing.assert_array_equal(cnt, cnt0, err_msg=cfg.label)


_ADVERSARIAL = {
    "self_loops": ([(v, v) for v in range(N_V)]
                   + [(0, 1), (1, 0), (5, 5), (5, 5)]),
    "duplicate_edges": [(2, 3)] * 40 + [(3, 2)] * 7,
    "all_into_one_vertex": [(s, 9) for s in range(N_V) for _ in (0, 1)],
    "single_edge": [(7, 11)],
    "isolated_vertices": [(0, 1), (1, 2), (2, 0)],  # 21 vertices untouched
    "hub_plus_singletons": ([(s % N_V, 4) for s in range(90)]
                            + [(8, 9), (10, 11)]),
}


@pytest.mark.parametrize("case", sorted(_ADVERSARIAL))
@pytest.mark.parametrize("monoid", [MIN, MAX, SUM, OR],
                         ids=lambda m: m.name)
def test_csr_adversarial_fixtures(case, monoid):
    """Named adversarial shapes × every monoid × every variant."""
    _assert_variants_match(monoid, *_edges_from_pairs(_ADVERSARIAL[case]))


# -- frontier filtering ------------------------------------------------------
def test_csr_all_inactive_frontier_yields_identity():
    """active ≡ False masks every edge: all-identity aggregate, zero
    counts — the fused loop's convergence iteration."""
    g = generate.rmat(N_V, 200, seed=5)
    prog = _program(MIN)
    state, aux = _state_for(MIN)
    active = np.zeros(N_V, bool)
    for cfg in TEST_SPACE:
        agg, cnt = _run_cfg(cfg, prog, state, aux, g.src, g.dst,
                            g.weights, active=active)
        assert (agg == np.float32(MIN.identity)).all(), cfg.label
        assert not cnt.any(), cfg.label


@settings(max_examples=10, deadline=None)
@given(_pairs, st.lists(st.integers(0, N_V - 1), min_size=0, max_size=10))
def test_csr_frontier_matches_filtered_oracle(pairs, active_ids):
    """Per-edge frontier filtering (emask & active[gsrc]) equals the
    oracle run on the filtered edge list — bit-identically for min."""
    src, dst, w = _edges_from_pairs(pairs)
    active = np.zeros(N_V, bool)
    active[np.asarray(active_ids, np.int64)] = True
    _assert_variants_match(MIN, src, dst, w, active=active)


# -- daemon-level differential ----------------------------------------------
def test_csr_daemon_run_blocks_matches_reference_daemon():
    """VectorizedDaemon kernel="pallas" (the CSR path) returns the same
    (agg, cnt) as kernel="reference" for a partial block selection —
    block-granularity skipping maps exactly onto the per-edge mask."""
    from repro.plug.daemons import VectorizedDaemon

    g = generate.rmat(300, 2500, seed=13)
    prog = sssp_bf(g)
    part = partition_contiguous(g, 1)[0]
    bs = build_blocks(part, 128)
    state, aux = prog.init(g)
    sel = np.arange(bs.num_blocks)[::2]  # every other block active
    outs = {}
    for kernel in ("reference", "pallas"):
        d = VectorizedDaemon(kernel=kernel).bind(prog, g.num_vertices)
        outs[kernel] = d.run_blocks(state, aux, bs, sel, {})
    np.testing.assert_array_equal(outs["reference"][0], outs["pallas"][0])
    np.testing.assert_array_equal(outs["reference"][1], outs["pallas"][1])


def test_csr_unknown_monoid_raises_in_every_variant():
    """An unregistered monoid must raise (with its name) from every merge
    family, never silently merge with the wrong operator."""
    weird = Monoid("product", 1.0, jnp.multiply, idempotent=False)
    prog = VertexProgram(
        name="weird", state_width=2, aux_width=1, monoid=weird,
        msg_gen=lambda s, d, w, a: s * w,
        msg_apply=lambda *a: None, init=lambda g: None)
    state, aux = _state_for(MIN)
    g = generate.rmat(N_V, 60, seed=1)
    for cfg in TEST_SPACE:
        with pytest.raises(ValueError, match="product"):
            _run_cfg(cfg, prog, state, aux, g.src, g.dst, g.weights)


# -- autotune ----------------------------------------------------------------
def test_autotune_cache_hit_skips_resweep():
    """Identically-shaped second bind is a pure cache lookup: the sweep
    counter must not move (the regression the issue pins — re-sweeping
    on every bind would swamp short runs)."""
    g = generate.rmat(N_V, 150, seed=8)
    prog = _program(MIN)
    cache = AutotuneCache()
    cfg1 = autotune_csr(g.src, g.dst, g.weights, N_V, prog, cache=cache,
                        repeats=1)
    assert (cache.sweeps, cache.hits) == (1, 0)
    cfg2 = autotune_csr(g.src, g.dst, g.weights, N_V, prog, cache=cache,
                        repeats=1)
    assert (cache.sweeps, cache.hits) == (1, 1)  # no re-sweep
    assert cfg1 is cfg2
    # a different shape is a different signature: sweeps again
    g2 = generate.rmat(N_V, 90, seed=8)
    autotune_csr(g2.src, g2.dst, g2.weights, N_V, prog, cache=cache,
                 repeats=1)
    assert cache.sweeps == 2


def test_autotune_report_records_full_sweep_table():
    """The report (exported into BENCH_plug.json) carries the chosen
    config and a timing for EVERY point of the space — the sweep is
    auditable, not just its winner."""
    g = generate.rmat(N_V, 150, seed=8)
    prog = _program(MIN)
    cache = AutotuneCache()
    chosen = autotune_csr(g.src, g.dst, g.weights, N_V, prog, cache=cache,
                          repeats=1)
    rep = cache.report()
    assert rep["sweeps"] == 1
    (entry,) = rep["entries"]
    assert entry["monoid"] == "min"
    assert entry["chosen"] == chosen.label
    labels = {c.label for c in DEFAULT_SPACE}
    assert set(entry["table"]) == labels
    assert all(t > 0 for t in entry["table"].values())
    assert entry["table"][chosen.label] == min(entry["table"].values())


def test_or_monoid_contract():
    """OR is registered, idempotent, identity 0, and equals numpy
    logical-or on indicator messages through both reduce paths."""
    from repro.core.template import MONOIDS

    assert MONOIDS["or"] is OR and OR.idempotent and OR.identity == 0.0
    rng = np.random.default_rng(0)
    msgs = (rng.random((50, 2)) < 0.4).astype(np.float32)
    seg = np.sort(rng.integers(0, 8, 50)).astype(np.int32)
    out = np.asarray(OR.segment_reduce(jnp.asarray(msgs),
                                       jnp.asarray(seg), 8))
    want = np.zeros((8, 2), np.float32)
    np.logical_or.at(want.astype(bool), seg, msgs.astype(bool))
    for s in range(8):
        m = msgs[seg == s]
        exp = m.any(axis=0).astype(np.float32) if m.size else 0.0
        np.testing.assert_array_equal(out[s], exp)
    # host scatter path agrees
    host = np.zeros((8, 2), np.float32)
    OR.scatter_at(host, seg, msgs)
    np.testing.assert_array_equal(host, out)
