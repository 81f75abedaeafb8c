"""Fig. 8 analogue: acceleration ratio of the middleware-attached engine
over the no-accelerator upper system.

Competitors (``repro.plug`` daemons behind one ``run_blocks`` contract):
  naive       — per-edge host loop ("GraphX/PowerGraph without accelerator")
  blocked     — daemon block programs, sequential 3-step flow
  vectorized  — fused-jit daemon (this repo's optimized path)
The paper reports 4–25× for CPU/GPU accelerators; on one CPU core the
vectorized/jit path plays the accelerator role.

A second table compares the multi-shard schedules at 8 shards on the
same workloads — ``vectorized`` (8 sequential daemon calls + host
merge), ``pipelined`` (3-stage overlap per shard), and ``sharded`` (one
device-resident ``shard_map`` program per iteration over an 8-device
host mesh; the fused drive loop) — so the acceleration of the
device-resident path is directly measurable against Fig. 8's baselines.

A third table sweeps the fused loop itself: ``daemon="sharded"`` ×
``kernel={reference, pallas}`` (the shard_map body: the dense gather/
scatter reference vs the fused CSR tile path, whose Pallas kernel only
interprets on CPU) × ``model={bsp, async}`` (the barriered fused step
vs the priority/staleness async step), per-iteration steady-state
times, plus the pallas/reference ratio per model.

A fault-recovery row (DESIGN.md §4.4) kills a device mid-run via
``dist.fault.FailureSchedule`` and records what elastic recovery costs:
iterations to reconverge after the checkpoint-free migration vs the
uninterrupted run, the migration seconds (re-plan + re-stack + state
``device_put``; the recompile for the smaller axis lands in the next
iteration's wall time), and whether the recovered fixed point is
bit-identical (it must be — sssp's min monoid is idempotent).

A ``dynamic`` table (DESIGN.md §7) applies add-only mutation batches of
several sizes to a converged run and compares the incremental
dirty-frontier restart against a cold restart — dirty must win on small
batches for the idempotent workloads (sssp, wcc), while pagerank's sum
monoid records the honest ``cold_fallback`` arm.

``--quick`` runs a reduced matrix and writes the ``BENCH_plug.json``
tier-2 baseline (scripts/verify.sh --tier2).

Environment note: since the sharded comparison was added, the whole
process runs on an 8-virtual-device host platform, which also perturbs
the single-shard naive/blocked/vectorized absolute times (the CPU is
split between virtual devices).  Baselines are comparable from that
change onward, not against earlier single-device recordings; the
``_meta`` block records ``num_devices`` for exactly this reason.
"""
from __future__ import annotations

import argparse
import os

# Must precede jax backend init: the sharded comparison wants an 8-device
# host mesh.  Appended to (not replacing) any pre-set XLA_FLAGS so e.g. a
# dump flag in the environment doesn't silently shrink the mesh to 1
# device and mislabel the BENCH_plug.json baseline.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402

from benchmarks.common import DATASETS, save, timeit  # noqa: E402
from repro import plug  # noqa: E402
from repro.graph.algorithms import label_prop, pagerank, sssp_bf  # noqa: E402

DAEMONS = ("naive", "blocked", "vectorized")
SHARDED_DAEMONS = ("vectorized", "pipelined", "sharded")
SHARDED_KERNELS = ("reference", "pallas")
SHARDED_MODELS = ("bsp", "async")
SHARDS = 8


def _steady_state_per_iter(mw, iters: int, *, repeats: int = 3) -> float:
    """One measurement protocol for every per-iteration table: a warmup
    run excludes compile time, then the min over ``repeats`` timed runs
    of wall time divided by the iterations the run actually executed (in
    case the workload converges early).  Min, not median: per-iteration
    cells feed ratio comparisons (pallas vs reference), and the minimum
    is the least noisy estimator of the compute floor on a shared CPU."""
    mw.run(max_iterations=iters)  # warmup: compile
    best = float("inf")
    for _ in range(repeats):
        res = mw.run(max_iterations=iters)
        best = min(best, res.wall_time / max(1, res.iterations))
    return best


def _per_iter_times(g, prog, iters: int, *, block: int) -> dict:
    """Steady-state per-iteration wall time per daemon at SHARDS shards."""
    times = {}
    for daemon in SHARDED_DAEMONS:
        mw = plug.Middleware(
            g, prog, daemon=daemon,
            upper="mesh" if daemon == "sharded" else "host",
            num_shards=SHARDS,
            options=plug.PlugOptions(block_size=block))
        # repeats matches the kernel×model matrix: the "sharded" cell is
        # reused there as reference/bsp and must share its noise floor
        times[daemon] = _steady_state_per_iter(mw, iters, repeats=5)
    return times


def _sharded_matrix_times(g, prog, iters: int, *, block: int,
                          reuse: dict | None = None) -> dict:
    """The fused drive loop swept over kernel × computation model:
    per-iteration steady-state wall time for daemon="sharded" with the
    reference vs Pallas shard_map body under the barriered (bsp) vs the
    priority/staleness (async) fused step.  ``reuse`` injects cells
    another table already measured (the shards8 "sharded" row IS
    reference/bsp), so each configuration is recorded exactly once."""
    rows = dict(reuse or {})
    for kernel in SHARDED_KERNELS:
        for model in SHARDED_MODELS:
            key = f"{kernel}/{model}"
            if key in rows:
                continue
            mw = plug.Middleware(
                g, prog, daemon=plug.get_daemon("sharded", kernel=kernel),
                upper="mesh", model=model, num_shards=SHARDS,
                options=plug.PlugOptions(block_size=block))
            if not mw._fused:  # survives python -O, unlike assert
                raise RuntimeError(
                    f"sharded matrix cell {key} fell back to the host "
                    "loop; refusing to record it as a fused baseline")
            # 5 repeats, not 3: these ~2ms cells feed the pallas vs
            # reference ratio, where single-run jitter flips the verdict
            rows[key] = _steady_state_per_iter(mw, iters, repeats=5)
    # ratio the issue pins: the CSR pallas path must not lose to the
    # reference shard_map body under either computation model.  Direct
    # indexing on purpose — a silently missing cell must KeyError here,
    # not vanish from the summary.
    ratios = {m: rows[f"pallas/{m}"] / rows[f"reference/{m}"]
              for m in SHARDED_MODELS}
    return rows, ratios


def _fault_recovery_row(g, *, block: int) -> dict:
    """Kill-at-iteration-k elastic recovery on the fused sharded loop.

    One uninterrupted sssp run to the fixed point, then the same
    composition with ``FailureSchedule`` killing a device at iteration 3
    — the run migrates onto the survivor mesh checkpoint-free and
    reconverges.  Records iterations-to-reconverge vs the uninterrupted
    count, the migration seconds, and the bit-identity of the recovered
    fixed point (sssp's min monoid is idempotent, so anything but
    ``True`` is a correctness regression, not noise).
    """
    prog = sssp_bf(g)

    def build(failures=None):
        return plug.Middleware(
            g, prog, daemon="sharded", upper="mesh", num_shards=SHARDS,
            failures=failures, options=plug.PlugOptions(block_size=block))

    ref = build().run(max_iterations=300)
    kill_it, kill_dev = 3, 2
    res = build(plug.FailureSchedule(kills=[(kill_it, kill_dev)])).run(
        max_iterations=300)
    mig = next(r["migration"] for r in res.per_iteration
               if "migration" in r)
    if not (ref.converged and res.converged):
        raise RuntimeError("fault-recovery row did not reconverge; "
                           "refusing to record it as a baseline")
    return {
        "algorithm": "sssp_bf",
        "kill": {"iteration": kill_it, "device": kill_dev},
        "iterations_uninterrupted": ref.iterations,
        "iterations_to_reconverge": res.iterations,
        "migration_s": mig["seconds"],
        "devices_before": mig["devices_before"],
        "devices_after": mig["devices_after"],
        "state_bit_identical": bool(np.array_equal(ref.state, res.state)),
    }


def _compressed_train_row(steps: int) -> dict:
    """The int8 error-feedback gradient wire (train.step grad_wire) vs
    the uncompressed step on a tiny model: median step time, the loss
    trajectory, the delayed-gradient mass at the end, and the wire-byte
    accounting — the training-side twin of the sync-compression rows.
    """
    import time as _time

    import jax

    from repro.configs import get_reduced
    from repro.dist.collectives import collective_bytes_saved
    from repro.models.model import Model
    from repro.train.data import SyntheticLM
    from repro.train.optimizer import AdamW, AdamWConfig
    from repro.train.step import init_wire_state, make_train_step

    cfg = get_reduced("stablelm-1.6b").replace(num_layers=2, dtype="float32",
                                               param_dtype="float32")
    model = Model(cfg)
    rows: dict = {}
    for wire in (None, "int8"):
        params, _ = model.init(jax.random.PRNGKey(0))
        opt = AdamW(AdamWConfig(peak_lr=3e-3, warmup_steps=2,
                                total_steps=steps))
        opt_state = opt.init(params)
        jitted = jax.jit(make_train_step(model, opt, grad_wire=wire))
        data = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=8, seed=1)
        ws = init_wire_state(params) if wire else None
        losses, times = [], []
        metrics = {}
        for _ in range(steps):
            batch = {k: jax.numpy.asarray(v)
                     for k, v in data.next_batch().items()}
            t0 = _time.perf_counter()
            if wire:
                params, opt_state, ws, metrics = jitted(params, opt_state,
                                                        ws, batch)
            else:
                params, opt_state, metrics = jitted(params, opt_state, batch)
            losses.append(float(metrics["loss"]))  # blocks: real step time
            times.append(_time.perf_counter() - t0)
        row = {"step_time_s": float(np.median(times[2:])),
               "loss_first": losses[0], "loss_last": losses[-1]}
        if wire:
            row["grad_wire_err"] = float(metrics["grad_wire_err"])
        rows[wire or "baseline"] = row
    # the wire a real pod would carry: the bf16 gradient volume vs int8
    grad_bytes = sum(int(np.prod(p.shape)) * 2
                     for p in jax.tree.leaves(params))
    rows["wire_bytes_baseline"] = grad_bytes
    rows["wire_bytes_saved"] = collective_bytes_saved(grad_bytes)
    rows["step_time_ratio"] = (rows["int8"]["step_time_s"]
                               / rows["baseline"]["step_time_s"])
    rows["loss_delta_last"] = (rows["int8"]["loss_last"]
                               - rows["baseline"]["loss_last"])
    rows["steps"] = steps
    return rows


def _oocore_table(quick: bool, *, stream_edges: int | None = None) -> dict:
    """Resident vs out-of-core vs out-of-core-without-prefetch at several
    HBM budgets (DESIGN.md §6).

    The workload is sssp on the road-network lattice — the canonical
    out-of-core traversal: a wavefront frontier that touches a narrow
    band of super-shards per iteration.  Two speedups are recorded per
    budget.  ``prefetch_speedup`` is the full-run mean; the acceptance
    number is ``sparse_slice.prefetch_speedup``, measured on the recorded
    iterations where the frontier left at least half the cold
    super-shards with no active source — there the prefetch scheduler
    skips their uploads *and* their identity-contributing compute, while
    the no-prefetch baseline (a plain synchronous streaming loop, no
    scheduler) still streams every group.  On this host the mesh is 8
    virtual devices on one CPU core, so transfer *hiding* contributes
    little (``overlap_efficiency`` stays low and dense-frontier
    iterations run near 1×); on an accelerator-attached host the same
    schedule additionally hides the device_put behind compute.
    """
    import jax

    from repro.graph import generate
    from repro.oocore import OocoreConfig

    side = 120 if quick else 200
    iters = 30 if quick else 60
    g = generate.grid_road(side, seed=1)
    prog = sssp_bf(g)

    def build(oc=None):
        return plug.Middleware(g, prog, daemon="sharded", upper="mesh",
                               num_shards=SHARDS, oocore=oc,
                               options=plug.PlugOptions(block_size=256))

    resident = build()
    total_dev = (sum(x.nbytes for x in jax.tree.leaves(resident.daemon.stacked))
                 // resident.daemon.m)
    resident.run(max_iterations=iters)  # compile
    ref = resident.run(max_iterations=iters)
    resident_per_iter = ref.wall_time / max(1, ref.iterations)

    def arm(budget, pf):
        mw = build(OocoreConfig(hbm_budget=budget, hot_fraction=0.25,
                                prefetch=pf))
        mw.run(max_iterations=iters)  # compile
        res = mw.run(max_iterations=iters)
        return mw, res, [r["oocore"]["seconds"] for r in res.per_iteration]

    rows = []
    best_sparse = 0.0
    for div in ((4, 8) if quick else (2, 4, 8)):
        budget = int(total_dev // div)
        pf_mw, pf_res, pf_t = arm(budget, True)
        npf_mw, npf_res, npf_t = arm(budget, False)
        st = pf_mw.oocore_stats
        ss = int(st["super_shards"])
        sparse = [i for i, r in enumerate(pf_res.per_iteration)
                  if r["oocore"]["skipped"] * 2 >= ss]

        def _speed(idx):
            denom = sum(pf_t[i] for i in idx)
            return sum(npf_t[i] for i in idx) / denom if denom else None

        # iteration 1 pays first-touch costs in both arms; the table is
        # steady-state like every other per-iteration cell here
        full = list(range(1, min(len(pf_t), len(npf_t))))
        sparse_speed = _speed(sparse) if sparse else None
        if sparse_speed:
            best_sparse = max(best_sparse, sparse_speed)
        rows.append({
            "hbm_budget": budget,
            "budget_fraction": 1.0 / div,
            "fits_resident": bool(pf_mw.daemon.oocore_plan.fits_resident),
            "super_shards": ss,
            "hot_cols": int(pf_mw.daemon.oocore_plan.hot_cols),
            "per_iter_s": {
                "resident": resident_per_iter,
                "oocore_prefetch": float(np.mean([pf_t[i] for i in full])),
                "oocore_no_prefetch": float(np.mean([npf_t[i] for i in full])),
            },
            "prefetch_speedup": _speed(full),
            "sparse_slice": {
                "iterations": ([min(sparse) + 1, max(sparse) + 1]
                               if sparse else None),
                "count": len(sparse),
                "prefetch_speedup": sparse_speed,
            },
            "overlap_efficiency": float(st["overlap_efficiency"]),
            "hot_hit_rate": float(st["hot_hit_rate"]),
            "skipped_super_shards": int(st["skipped"]),
            "uploads": int(st["uploads"]),
            "upload_bytes": int(st["upload_bytes"]),
            "bit_identical": bool(np.array_equal(pf_res.state, ref.state)
                                  and np.array_equal(npf_res.state, ref.state)),
        })
    out = {
        "algorithm": "sssp_bf",
        "graph": {"generator": "grid_road", "side": side,
                  "num_vertices": g.num_vertices, "num_edges": g.num_edges},
        "iterations": iters,
        "column_bytes_per_device": int(total_dev),
        "hot_fraction": 0.25,
        "budgets": rows,
        "best_sparse_speedup": best_sparse,
    }
    if stream_edges:
        out["stream"] = _oocore_stream_row(stream_edges)
    return out


def _oocore_stream_row(edges: int) -> dict:
    """The big-input invocation (README: ``--oocore-edges 12000000``):
    build a power-law graph with the streaming generator — the only one
    that stays edge-list-native at >10⁷ edges — and run an out-of-core
    pagerank slice with an explicit super-shard split, recording
    generation time, per-iteration time, and the degree-ordered hot
    set's hit rate (power-law inputs are where the cache earns its keep:
    a small resident prefix covers most of the edge mass)."""
    import time as _time

    from repro.graph import generate
    from repro.oocore import OocoreConfig

    t0 = _time.perf_counter()
    g = generate.rmat_stream(max(1 << 10, edges // 12), edges, seed=1)
    gen_s = _time.perf_counter() - t0
    mw = plug.Middleware(
        g, pagerank(g), daemon="sharded", upper="mesh", num_shards=SHARDS,
        oocore=OocoreConfig(num_super_shards=8, hot_fraction=0.25),
        options=plug.PlugOptions(block_size=1024))
    res = mw.run(max_iterations=3)
    st = mw.oocore_stats
    plan = mw.daemon.oocore_plan
    return {
        "generator": "rmat_stream",
        "num_vertices": g.num_vertices,
        "num_edges": g.num_edges,
        "generate_s": gen_s,
        "iterations": res.iterations,
        "per_iter_s": res.wall_time / max(1, res.iterations),
        "super_shards": int(plan.num_super_shards),
        "hot_cols": int(plan.hot_cols),
        "hot_hit_rate": float(st["hot_hit_rate"]),
        "overlap_efficiency": float(st["overlap_efficiency"]),
        "upload_bytes": int(st["upload_bytes"]),
    }


def _dynamic_table(quick: bool) -> dict:
    """Dynamic graphs (DESIGN.md §7): the incremental dirty-frontier
    restart vs a cold restart across update-batch sizes.

    Per cell the middleware converges to a fixed point, applies an
    add-only edge batch (publishing a ``"mutation"`` structure epoch
    that recuts only dirty shards), then times — post-compile, so the
    epoch's recompile is off the clock for both arms — a full cold
    restart and the incremental restart that resumes from the previous
    fixed point with only the dirty frontier active.  sssp (min) and
    wcc (min over the symmetrized graph) have idempotent monoids, so
    the incremental restart is sound and must land bit-identical to
    cold; pagerank (sum) records the ``cold_fallback`` arm — the epoch
    layer still recuts tiles incrementally, but the restart cannot
    reuse the old fixed point.  The acceptance this table guards: at
    the smallest batch, the dirty restart beats the cold restart for
    at least one idempotent workload (it should for both)."""
    from repro.graph import generate
    from repro.graph.algorithms import wcc
    from repro.graph.mutation import MutationLog

    if quick:
        g0 = generate.rmat(300, 2_400, seed=1)
        block = 256
    else:
        g0 = generate.rmat(2_000, 20_000, seed=1)
        block = 1024
    sizes = (8, 64, 512)
    cap = 300
    out = {"_meta": {"batch_sizes": list(sizes),
                     "graph": {"num_vertices": g0.num_vertices,
                               "num_edges": g0.num_edges}}}
    algs = (("pagerank", pagerank, False), ("sssp_bf", sssp_bf, False),
            ("wcc", wcc, True))
    for name, algf, symmetric in algs:
        # wcc's monoid is only meaningful on a symmetrized graph, and a
        # symmetric batch must stay symmetric: add both directions
        g = g0.with_reverse_edges() if symmetric else g0
        rows = {}
        for batch_size in sizes:
            prog = algf(g)
            mw = plug.Middleware(
                g, prog, daemon="sharded", upper="mesh", num_shards=SHARDS,
                options=plug.PlugOptions(block_size=block))
            base = mw.run(max_iterations=cap)
            prev0 = np.asarray(base.state)
            rng = np.random.default_rng(1_000 + batch_size)
            log = MutationLog()
            pairs = max(1, batch_size // 2) if symmetric else batch_size
            for _ in range(pairs):
                s, d = (int(v) for v in rng.integers(0, g.num_vertices, 2))
                if s == d:
                    d = (d + 1) % g.num_vertices
                w = float(rng.uniform(0.1, 2.0))
                log.add_edge(s, d, w)
                if symmetric:
                    log.add_edge(d, s, w)
            # applies the batch and runs once on the mutated structure:
            # the new epoch's compile lands here, off both timed arms
            mw.run_dynamic(log, max_iterations=cap)
            restart = dict(mw.last_restart)
            meta = mw.epochs.epoch.meta
            frontier = meta["frontier"]

            cold_s, it_cold, cold_state = float("inf"), 0, None
            for _ in range(3):
                rc = mw.run(max_iterations=cap)
                if rc.wall_time < cold_s:
                    cold_s, it_cold = rc.wall_time, rc.iterations
                cold_state = np.asarray(rc.state)
            cell = {
                "edges_added": int(meta["edges_added"]),
                "mode": restart["mode"],
                "reason": restart["reason"],
                "dirty_count": int(meta["dirty_count"]),
                "shards_recut": int(meta["shards_recut"]),
                "shards_clean": int(meta["shards_clean"]),
                "mutation_apply_s": float(meta["seconds"]),
                "cold_s": cold_s,
                "iterations_cold": int(it_cold),
            }
            if restart["mode"] == "dirty":
                def init(gr, _s=prev0, _i=prog.init):
                    return _s, _i(gr)[1]

                dirty_s, it_dirty, dirty_state = float("inf"), 0, None
                for _ in range(3):
                    rd = mw.run(max_iterations=cap, init=init,
                                frontier=frontier)
                    if rd.wall_time < dirty_s:
                        dirty_s, it_dirty = rd.wall_time, rd.iterations
                    dirty_state = np.asarray(rd.state)
                cell.update({
                    "dirty_s": dirty_s,
                    "iterations_dirty": int(it_dirty),
                    "speedup": cold_s / dirty_s,
                    "bit_identical": bool(
                        np.array_equal(dirty_state, cold_state)),
                })
            else:
                cell.update({"dirty_s": None, "iterations_dirty": None,
                             "speedup": None, "bit_identical": None})
            rows[f"b{batch_size}"] = cell
        out[name] = rows
    small = f"b{min(sizes)}"
    winners = [name for name, _, _ in algs
               if (out[name][small].get("speedup") or 0.0) > 1.0]
    if not winners:
        raise RuntimeError(
            "dynamic table: no idempotent workload's dirty restart beat "
            f"its cold restart at the smallest batch ({small}); refusing "
            "to record it as a baseline")
    out["_meta"]["smallest_batch_winners"] = winners
    return out


def _compressed_wire_row(g, *, block: int, iters: int) -> dict:
    """``MeshUpperSystem(wire="compressed")`` accuracy and volume on the
    sum-monoid workloads (the int8 error-feedback sync wire only admits
    summed aggregates; min/max merges must stay exact).  Both arms run
    the same host-loop composition — ``daemon="vectorized"`` under the
    mesh upper — so the only difference is the wire, and the byte
    counters come from the upper system's own accounting."""
    rows = {}
    for name, algf in (("pagerank", pagerank), ("label_prop", label_prop)):
        prog = algf(g)
        arms = {}
        for wire in ("exact", "compressed"):
            mw = plug.Middleware(
                g, prog, daemon="vectorized",
                upper=plug.MeshUpperSystem(wire=wire), num_shards=SHARDS,
                options=plug.PlugOptions(block_size=block))
            per_iter = _steady_state_per_iter(mw, iters)
            res = mw.run(max_iterations=iters)
            arms[wire] = {"per_iter_s": per_iter,
                          "state": np.asarray(res.state),
                          "wire_stats": dict(mw.upper.wire_stats)}
        ws = arms["compressed"]["wire_stats"]
        err = np.abs(arms["compressed"]["state"] - arms["exact"]["state"])
        rows[name] = {
            "per_iter_s": {w: arms[w]["per_iter_s"] for w in arms},
            "max_abs_err": float(err.max()),
            "mean_abs_err": float(err.mean()),
            "exact_bytes": int(arms["exact"]["wire_stats"]["exact_bytes"]),
            "compressed_bytes": int(ws["compressed_bytes"]),
            "volume_ratio": (ws["compressed_bytes"]
                             / max(1, arms["exact"]["wire_stats"]["exact_bytes"])),
        }
    return rows


ASYNC_SKEW_ARMS = (
    # name, theta0, decay, bucket_k
    ("eager", 0.0, 0.5, 0),        # theta collapses immediately; holds come
                                   # only from owner-empty private frontiers
    ("holding", 10.0, 0.9, 0),     # predict half holds low-priority devices
    ("buckets", 10.0, 0.9, 8),     # held devices still run top-k residual
                                   # vertices through the bucket kernel
)


def _validate_async_skew(table: dict) -> dict:
    """Refuse to record an async_skew table that does not demonstrate the
    claim it exists to pin.  Every async arm must (a) actually skip Gen
    work — a "hold" that still executes its blocks is the bug this table
    guards against, so zero skipped device-iterations is a recording
    error, not a data point; (b) reach the same fixed point bit-for-bit
    as BSP (sssp's min monoid is idempotent, so async reordering must be
    invisible in the result); and (c) beat BSP per-iteration in the
    skewed steady state (async_vs_bsp < 1.0) — otherwise the conditional
    execution is not paying for its scheduling overhead and the table
    would pin a regression as a baseline."""
    for name, row in table["configs"].items():
        if row["gen_skipped"] <= 0:
            raise RuntimeError(
                f"async_skew[{name}]: gen_skipped=0 — predicted holds "
                "executed Gen anyway; refusing to record")
        if not row["bit_identical"]:
            raise RuntimeError(
                f"async_skew[{name}]: async fixed point diverged from "
                "BSP under an idempotent monoid; refusing to record")
        if not row["async_vs_bsp"] < 1.0:
            raise RuntimeError(
                f"async_skew[{name}]: async_vs_bsp="
                f"{row['async_vs_bsp']:.3f} >= 1.0 — async did not beat "
                "BSP on the skewed graph; refusing to record")
    return table


def _async_skew_table(quick: bool) -> dict:
    """Async vs BSP on a skewed power-law graph where most devices have
    nothing useful to do most iterations.  The rmat multiset (dedup off)
    keeps the full hub-heavy edge distribution, and sssp from 4 seed
    sources gives owner-filtered private frontiers that stay empty on
    non-hub devices — exactly the regime conditional Gen execution is
    for.  Two measurements per arm: the bench-standard fixed-window
    per-iteration steady state (ratio against BSP is the gated claim),
    and one full run to convergence for the skipped-Gen accounting and
    the bit-identical fixed-point check."""
    from repro.graph import generate
    g = generate.rmat(1_000, 64_000, seed=7, a=0.7, b=0.15, c=0.1,
                      dedup=False)
    prog = sssp_bf(g)
    frontier = np.zeros(g.num_vertices, dtype=bool)
    frontier[:4] = True
    opts = plug.PlugOptions(block_size=1024)
    window = 6
    repeats = 3 if quick else 5

    def _mk(model):
        return plug.Middleware(g, prog, daemon="sharded", upper="mesh",
                               model=model, num_shards=SHARDS, options=opts)

    def _window_per_iter(mw):
        mw.run(max_iterations=window, frontier=frontier)  # warmup: compile
        best = float("inf")
        for _ in range(repeats):
            res = mw.run(max_iterations=window, frontier=frontier)
            best = min(best, res.wall_time / max(1, res.iterations))
        return best

    bsp_per_iter = _window_per_iter(_mk("bsp"))
    bsp_full = _mk("bsp").run(max_iterations=200, frontier=frontier)
    if not bsp_full.converged:
        raise RuntimeError("async_skew: BSP baseline failed to converge")
    ref = np.asarray(bsp_full.state)
    configs = {}
    for name, theta0, decay, bucket_k in ASYNC_SKEW_ARMS:
        model = plug.AsyncModel(theta0=theta0, decay=decay,
                                bucket_k=bucket_k)
        per_iter = _window_per_iter(_mk(model))
        full = _mk(model).run(max_iterations=200, frontier=frontier)
        if not full.converged:
            raise RuntimeError(f"async_skew[{name}]: failed to converge")
        gen_skipped = sum(r["gen_skipped"] for r in full.per_iteration)
        gen_total = sum(r["gen_skipped"] + r["gen_run"]
                        for r in full.per_iteration)
        configs[name] = {
            "theta0": theta0, "decay": decay, "bucket_k": bucket_k,
            "per_iter_s": per_iter,
            "async_vs_bsp": per_iter / bsp_per_iter,
            "iterations": full.iterations,
            "gen_skipped": int(gen_skipped),
            "gen_total": int(gen_total),
            "skip_fraction": gen_skipped / max(1, gen_total),
            "bit_identical": bool(
                np.array_equal(ref, np.asarray(full.state))),
        }
    table = {
        "algorithm": "sssp_bf",
        "graph": {"num_vertices": g.num_vertices,
                  "num_edges": g.num_edges,
                  "rmat": {"a": 0.7, "b": 0.15, "c": 0.1, "seed": 7,
                           "dedup": False}},
        "num_shards": SHARDS,
        "num_sources": 4,
        "window_iterations": window,
        "bsp": {"per_iter_s": bsp_per_iter,
                "iterations": bsp_full.iterations},
        "configs": configs,
    }
    return _validate_async_skew(table)


def run(small: bool = True, quick: bool = False,
        oocore_edges: int | None = None) -> dict:
    g = DATASETS["orkut-mini"]()
    if quick:  # tier-2 CI slice: small graph, few iterations
        from repro.graph import generate
        g = generate.rmat(300, 2_400, seed=1)
        iters = {"pagerank": 2, "sssp_bf": 3, "label_prop": 2}
    elif small:  # naive is O(E) python per iteration — subsample for speed
        from repro.graph import generate
        g = generate.rmat(2_000, 20_000, seed=1)
        iters = {"pagerank": 5, "sssp_bf": 8, "label_prop": 5}
    else:
        iters = {"pagerank": 5, "sssp_bf": 8, "label_prop": 5}
    algs = {"pagerank": pagerank, "sssp_bf": sssp_bf, "label_prop": label_prop}
    out = {}
    for name, algf in algs.items():
        prog = algf(g)
        times = {}
        for daemon in DAEMONS:
            mw = plug.Middleware(
                g, prog, daemon=daemon, num_shards=1,
                options=plug.PlugOptions(block_size=2048))
            times[daemon] = timeit(
                lambda m=mw: m.run(max_iterations=iters[name]),
                repeat=1, warmup=0)
        per_iter = _per_iter_times(g, prog, iters[name],
                                   block=256 if quick else 1024)
        matrix, ratios = _sharded_matrix_times(
            g, prog, iters[name], block=256 if quick else 1024,
            reuse={"reference/bsp": per_iter["sharded"]})
        out[name] = {
            **times,
            "speedup_blocked": times["naive"] / times["blocked"],
            "speedup_vectorized": times["naive"] / times["vectorized"],
            "shards8": {
                "num_shards": SHARDS,
                "per_iter_s": per_iter,
                "speedup_sharded_vs_vectorized":
                    per_iter["vectorized"] / per_iter["sharded"],
                "speedup_sharded_vs_pipelined":
                    per_iter["pipelined"] / per_iter["sharded"],
            },
            "sharded_matrix": {
                "num_shards": SHARDS,
                "kernels": list(SHARDED_KERNELS),
                "models": list(SHARDED_MODELS),
                "per_iter_s": matrix,
                "pallas_vs_reference": ratios,
            },
        }
    out["fault_recovery"] = _fault_recovery_row(g,
                                                block=256 if quick else 1024)
    out["compressed_train"] = _compressed_train_row(steps=8 if quick else 20)
    out["oocore"] = _oocore_table(quick, stream_edges=oocore_edges)
    out["compressed_wire"] = _compressed_wire_row(
        g, block=256 if quick else 1024,
        iters=iters["pagerank"] + 2)
    out["dynamic"] = _dynamic_table(quick)
    out["async_skew"] = _async_skew_table(quick)
    import jax
    out["_meta"] = {"api": "repro.plug.Middleware", "quick": quick,
                    "graph": {"num_vertices": g.num_vertices,
                              "num_edges": g.num_edges},
                    "iterations": iters,
                    "num_devices": len(jax.devices())}
    save("BENCH_plug" if quick else "bench_accel", out)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tier-2 slice; writes BENCH_plug.json baseline")
    ap.add_argument("--oocore-edges", type=int, default=None, metavar="E",
                    help="also stream-generate an E-edge power-law graph "
                         "(rmat_stream) and record an out-of-core pagerank "
                         "slice on it; E > 10^7 is the intended scale")
    args = ap.parse_args()
    results = run(quick=args.quick, oocore_edges=args.oocore_edges)
    fr = results.pop("fault_recovery")
    print(f"fault-recovery ({fr['algorithm']}): kill dev "
          f"{fr['kill']['device']} @ it {fr['kill']['iteration']} → "
          f"{fr['devices_before']}→{fr['devices_after']} devices, "
          f"migration {fr['migration_s']*1e3:.0f}ms, reconverged in "
          f"{fr['iterations_to_reconverge']} its "
          f"(uninterrupted {fr['iterations_uninterrupted']}), "
          f"bit-identical={fr['state_bit_identical']}")
    oc = results.pop("oocore")
    for row in oc["budgets"]:
        sp = row["sparse_slice"]
        print(f"oocore ({oc['algorithm']}, budget "
              f"{row['budget_fraction']:.0%} of columns): "
              f"ss={row['super_shards']} "
              f"pf={row['per_iter_s']['oocore_prefetch']*1e3:.1f}ms "
              f"npf={row['per_iter_s']['oocore_no_prefetch']*1e3:.1f}ms "
              f"speedup={row['prefetch_speedup']:.2f}x "
              f"(sparse slice {sp['iterations']}: "
              f"{sp['prefetch_speedup'] or float('nan'):.2f}x) "
              f"overlap={row['overlap_efficiency']:.2f} "
              f"hit={row['hot_hit_rate']:.2f} "
              f"bit-identical={row['bit_identical']}")
    if "stream" in oc:
        s = oc["stream"]
        print(f"oocore stream: {s['num_edges']} edges generated in "
              f"{s['generate_s']:.1f}s, pagerank "
              f"{s['per_iter_s']:.2f}s/iter over {s['super_shards']} "
              f"super-shards, hot hit rate {s['hot_hit_rate']:.2f}")
    dy = results.pop("dynamic")
    for alg in ("pagerank", "sssp_bf", "wcc"):
        cells = []
        for bkey, c in dy[alg].items():
            if c["mode"] == "dirty":
                cells.append(
                    f"{bkey} dirty={c['dirty_s']*1e3:.0f}ms "
                    f"cold={c['cold_s']*1e3:.0f}ms "
                    f"({c['speedup']:.1f}x, "
                    f"{c['iterations_dirty']}/{c['iterations_cold']} its, "
                    f"bit-identical={c['bit_identical']})")
            else:
                cells.append(f"{bkey} {c['mode']} "
                             f"cold={c['cold_s']*1e3:.0f}ms "
                             f"({c['iterations_cold']} its)")
        print(f"dynamic ({alg}): " + "  ".join(cells))
    ak = results.pop("async_skew")
    for name, row in ak["configs"].items():
        print(f"async-skew ({ak['algorithm']}, {name}): "
              f"async {row['per_iter_s']*1e3:.1f}ms/iter vs bsp "
              f"{ak['bsp']['per_iter_s']*1e3:.1f}ms/iter "
              f"(ratio {row['async_vs_bsp']:.2f}x), skipped Gen on "
              f"{row['gen_skipped']}/{row['gen_total']} device-iterations "
              f"({row['skip_fraction']:.0%}), "
              f"bit-identical={row['bit_identical']}")
    cw = results.pop("compressed_wire")
    for alg, row in cw.items():
        print(f"compressed-wire ({alg}): "
              f"{row['compressed_bytes']}/{row['exact_bytes']}B "
              f"({row['volume_ratio']:.2f}x volume), "
              f"max|err|={row['max_abs_err']:.2e}")
    ct = results.pop("compressed_train")
    print(f"compressed-train: int8 step {ct['int8']['step_time_s']*1e3:.0f}ms "
          f"vs baseline {ct['baseline']['step_time_s']*1e3:.0f}ms "
          f"(ratio {ct['step_time_ratio']:.2f}x), "
          f"loss delta {ct['loss_delta_last']:+.4f}, "
          f"wire saved {ct['wire_bytes_saved']}/{ct['wire_bytes_baseline']}B")
    for alg, r in results.items():
        if not (isinstance(r, dict) and "naive" in r):
            continue  # _meta
        print(f"{alg:12s} naive={r['naive']:.2f}s blocked={r['blocked']:.2f}s "
              f"vectorized={r['vectorized']:.3f}s "
              f"accel={r['speedup_vectorized']:.1f}x")
        s8 = r["shards8"]
        p = s8["per_iter_s"]
        print(f"{'':12s} @8 shards/iter: vectorized={p['vectorized']*1e3:.1f}ms "
              f"pipelined={p['pipelined']*1e3:.1f}ms "
              f"sharded={p['sharded']*1e3:.1f}ms "
              f"(sharded {s8['speedup_sharded_vs_vectorized']:.1f}x vs "
              f"vectorized)")
        mx = r["sharded_matrix"]["per_iter_s"]
        cells = " ".join(f"{k}={v*1e3:.1f}ms" for k, v in mx.items())
        print(f"{'':12s} sharded kernel×model/iter: {cells}")
        ratios = " ".join(
            f"{m}={v:.2f}x"
            for m, v in r["sharded_matrix"]["pallas_vs_reference"].items())
        print(f"{'':12s} pallas/reference ratio: {ratios}")


if __name__ == "__main__":
    main()
