"""Elastic fault tolerance (DESIGN.md §4.4): checkpoint-free state
migration of the fused drive loops under deterministic fault injection.

Covers the acceptance surface: a device killed mid-run via
``dist.fault.FailureSchedule`` is detected between fused iterations, the
run migrates onto the survivor mesh (orphaned shards reassigned with
Lemma 2) and reconverges to a fixed point bit-identical to the
uninterrupted reference for idempotent monoids (tolerance-close for
sum); straggler reports trigger a Lemma-2 re-partition; stale busy-time
samples recorded under the dead placement never leak into survivor
capacities."""
import os

# Must precede jax backend init (collection-time import, before any test
# body runs) — elasticity wants a multi-device host mesh to shrink.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro import plug  # noqa: E402
from repro.dist import fault  # noqa: E402
from repro.graph import generate  # noqa: E402
from repro.graph.algorithms import pagerank, sssp_bf, wcc  # noqa: E402

BLOCK = 256
SHARDS = 8
KILL_IT = 3
# async rows follow their own schedule; every fixed-point comparison
# runs the reference to convergence
REF_MAX_IT = 300

_ALGS = {"pagerank": pagerank, "sssp_bf": sssp_bf, "wcc": wcc}

_graph_cache: dict = {}
_cref_cache: dict = {}


def _graph(alg="sssp_bf"):
    if "g" not in _graph_cache:
        _graph_cache["g"] = generate.rmat(256, 2048, seed=9)
    g = _graph_cache["g"]
    return g.with_reverse_edges() if alg == "wcc" else g


def _converged_reference(alg):
    if alg not in _cref_cache:
        g = _graph(alg)
        _cref_cache[alg] = plug.run_reference(g, _ALGS[alg](g),
                                              max_iterations=REF_MAX_IT)[0]
    return _cref_cache[alg]


def _elastic(prog, g, *, model="bsp", kills=(), slow=(), monitor=None,
             kernel="reference"):
    return plug.Middleware(
        g, prog, daemon=plug.get_daemon("sharded", kernel=kernel),
        upper="mesh", model=model,
        num_shards=SHARDS, monitor=monitor,
        failures=plug.FailureSchedule(kills=kills, slow=slow),
        options=plug.PlugOptions(block_size=BLOCK))


def _migrations(res):
    return [r["migration"] for r in res.per_iteration if "migration" in r]


@pytest.mark.parametrize("model", ["bsp", "async"])
@pytest.mark.parametrize("alg", sorted(_ALGS))
def test_kill_equivalence_matrix(alg, model):
    """Acceptance: kill device 2 at iteration 3 on the 8-device mesh;
    the migrated run recovers without checkpoint restore and its fixed
    point is bit-identical to the uninterrupted reference for idempotent
    monoids (min: sssp/wcc), activity-tolerance-close for sum
    (pagerank)."""
    g = _graph(alg)
    prog = _ALGS[alg](g)
    mw = _elastic(prog, g, model=model, kills=[(KILL_IT, 2)])
    assert mw._fused_kind == ("async" if model == "async" else "bsp")
    res = mw.run(max_iterations=REF_MAX_IT)
    assert res.converged
    migs = _migrations(res)
    assert len(migs) == 1
    assert migs[0]["killed"] == [2]
    assert migs[0]["devices_after"] < migs[0]["devices_before"]
    assert 2 not in migs[0]["device_ids"]
    # the kill fired before iteration KILL_IT executed
    assert res.per_iteration[KILL_IT - 1]["iteration"] == KILL_IT
    assert "migration" in res.per_iteration[KILL_IT - 1]
    ref = _converged_reference(alg)
    if prog.monoid.idempotent:
        np.testing.assert_array_equal(ref, res.state)
    else:
        np.testing.assert_allclose(res.state, ref, atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("model", ["bsp", "async"])
def test_kill_mid_run_with_pallas_kernel(model):
    """The fused CSR tile daemon (kernel="pallas") survives a mid-run
    kill exactly like the reference kernel: bind_shards re-compacts and
    re-stacks the CSR tiles for the survivor mesh (with the config the
    daemon was bound with) and the fixed point stays bit-identical to the
    uninterrupted reference."""
    g = _graph("sssp_bf")
    prog = sssp_bf(g)
    mw = _elastic(prog, g, model=model, kills=[(KILL_IT, 2)],
                  kernel="pallas")
    assert mw._fused_kind == ("async" if model == "async" else "bsp")
    cfg_before_run = mw.daemon._csr_config
    res = mw.run(max_iterations=REF_MAX_IT)
    assert res.converged
    migs = _migrations(res)
    assert len(migs) == 1
    assert migs[0]["killed"] == [2]
    assert 2 not in migs[0]["device_ids"]
    # migration re-stacked tiles with the bound config
    assert mw.daemon._csr_config is cfg_before_run
    assert "csr" in mw.daemon.stacked  # still on the CSR fused path
    np.testing.assert_array_equal(_converged_reference("sssp_bf"),
                                  res.state)


def test_straggler_drift_triggers_second_migration():
    """Regression (satellite): straggler handling is continuous, not
    once-per-device.  A device flagged and migrated-around once keeps
    degrading; the monitor's capacity drift vs the acknowledged
    placement crosses the threshold and a SECOND migration fires —
    previously the fire-once ``_handled_stragglers`` set swallowed it."""
    g = _graph()
    prog = sssp_bf(g)
    slow = [(1, d, 5.0 if d == 5 else 1.0) for d in range(SHARDS)]
    slow += [(3, 5, 50.0)]  # same straggler, 10× worse after handling
    mw = _elastic(prog, g, slow=slow)
    res = mw.run(max_iterations=40)
    migs = _migrations(res)
    assert len(migs) == 2
    assert migs[0]["stragglers"] == [5]
    assert migs[1]["stragglers"] == [5]  # re-flagged via drift
    assert all(m["repartitioned"] for m in migs)
    sizes = np.array([p.num_edges for p in mw.partitions])
    assert sizes[5] == sizes.min()  # entitlement kept shrinking
    ref, _ = plug.run_reference(g, prog, max_iterations=40)
    np.testing.assert_array_equal(ref, res.state)
    # stable capacity afterwards: no further migrations on a re-run
    assert not _migrations(mw.run(max_iterations=40))


def test_migration_retargets_every_layer():
    """After the kill, daemon + upper share the survivor mesh, the dead
    device is gone from it, the shard count is conserved across the
    reassignment, and a second run() on the migrated middleware still
    matches the reference."""
    g = _graph()
    prog = sssp_bf(g)
    mw = _elastic(prog, g, kills=[(KILL_IT, 2)])
    res = mw.run(max_iterations=40)
    mig = _migrations(res)[0]
    assert mw.daemon.m == mw.upper.m == mig["devices_after"] == 4
    assert mw.daemon.mesh is mw.upper.mesh
    assert mw.monitor.failed[2] and mw.monitor.alive_hosts == 7
    assert 2 not in mw._mesh_device_ids
    mesh_devs = set(np.asarray(mw.upper.mesh.devices).reshape(-1).tolist())
    assert mw.fleet_devices[2] not in mesh_devs
    # every shard reassigned exactly once, never beyond cap
    counts = np.bincount(mig["assignment"], minlength=4)
    assert counts.sum() == SHARDS and counts.max() <= SHARDS // 4
    # the migrated composition stays healthy: fresh run, same answer
    res2 = mw.run(max_iterations=40)
    ref, _ = plug.run_reference(g, prog, max_iterations=40)
    np.testing.assert_array_equal(ref, res2.state)
    np.testing.assert_array_equal(ref, res.state)


def test_cascading_kills_migrate_twice():
    """Two kills at different iterations: the first shrinks the axis
    8→4, the second (hitting a device of the survivor mesh) re-plans
    again among the remaining survivors — still exact."""
    g = _graph()
    prog = sssp_bf(g)
    # after the first kill (device 1) the survivor mesh is [0, 2, 3, 4];
    # the second kill targets device 3, which sits in it
    mw = _elastic(prog, g, kills=[(2, 1), (4, 3)])
    res = mw.run(max_iterations=60)
    migs = _migrations(res)
    assert len(migs) == 2
    assert migs[0]["killed"] == [1] and migs[1]["killed"] == [3]
    assert migs[0]["device_ids"] == [0, 2, 3, 4]
    assert 3 not in migs[1]["device_ids"] and 1 not in migs[1]["device_ids"]
    assert mw.monitor.alive_hosts == 6
    ref, _ = plug.run_reference(g, prog, max_iterations=60)
    np.testing.assert_array_equal(ref, res.state)


def test_kill_of_unused_device_is_a_no_op():
    """A dead device that is not part of the active mesh (already
    migrated away) must not trigger another migration."""
    g = _graph()
    prog = sssp_bf(g)
    # first kill shrinks the mesh to [0, 2, 3, 4]; device 5 is not in it
    mw = _elastic(prog, g, kills=[(2, 1), (4, 5)])
    res = mw.run(max_iterations=60)
    migs = _migrations(res)
    assert len(migs) == 1 and migs[0]["killed"] == [1]
    assert mw.monitor.failed[5]  # marked dead, but nothing to migrate
    ref, _ = plug.run_reference(g, prog, max_iterations=60)
    np.testing.assert_array_equal(ref, res.state)


def test_straggler_report_triggers_lemma2_repartition():
    """Injected step-time reports flag device 5 as a straggler; the
    middleware re-partitions so its shard slots carry proportionally
    fewer edges (Lemma 2) on the unchanged mesh — and the run stays
    exact."""
    g = _graph()
    prog = sssp_bf(g)
    slow = [(2, d, 8.0 if d == 5 else 1.0) for d in range(SHARDS)]
    mw = _elastic(prog, g, slow=slow)
    res = mw.run(max_iterations=40)
    migs = _migrations(res)
    assert len(migs) == 1
    assert migs[0]["stragglers"] == [5] and migs[0]["killed"] == []
    assert migs[0]["repartitioned"]
    assert migs[0]["devices_after"] == migs[0]["devices_before"] == SHARDS
    sizes = np.array([p.num_edges for p in mw.partitions])
    # slot d sits on device d (cap=1); the straggler's slot shrank
    assert sizes[5] == sizes.min()
    assert sizes[5] < 0.5 * sizes.max()
    ref, _ = plug.run_reference(g, prog, max_iterations=40)
    np.testing.assert_array_equal(ref, res.state)
    # the same straggler does not re-trigger a migration every iteration
    res2 = mw.run(max_iterations=40)
    assert not _migrations(res2)


def test_straggler_outside_active_mesh_is_a_no_op():
    """A flagged straggler that carries no shards (not in the active
    mesh after an earlier migration) must not trigger a re-partition —
    same filter the failure branch applies."""
    g = _graph()
    prog = sssp_bf(g)
    # the kill shrinks the mesh to [0, 2, 3, 4]; device 6 then reports
    # slow but sits outside the active mesh
    slow = [(4, d, 8.0 if d == 6 else 1.0) for d in range(SHARDS)]
    mw = _elastic(prog, g, kills=[(2, 1)], slow=slow)
    res = mw.run(max_iterations=60)
    migs = _migrations(res)
    assert len(migs) == 1 and migs[0]["killed"] == [1]
    assert mw.monitor.stragglers()[6]  # flagged, but shard-less
    ref, _ = plug.run_reference(g, prog, max_iterations=60)
    np.testing.assert_array_equal(ref, res.state)


def test_external_mark_failed_migrates_without_schedule():
    """Monitor-only wiring: a failure marked externally (no
    FailureSchedule) is picked up by the between-iteration poll."""
    g = _graph()
    prog = sssp_bf(g)
    mon = fault.FleetMonitor(num_hosts=SHARDS, model_parallel=1)
    mw = plug.Middleware(g, prog, daemon="sharded", upper="mesh",
                         num_shards=SHARDS, monitor=mon,
                         options=plug.PlugOptions(block_size=BLOCK))
    mon.mark_failed(0)
    res = mw.run(max_iterations=40)
    migs = _migrations(res)
    assert len(migs) == 1 and migs[0]["killed"] == []
    assert 0 not in migs[0]["device_ids"]
    ref, _ = plug.run_reference(g, prog, max_iterations=40)
    np.testing.assert_array_equal(ref, res.state)


def test_elastic_wiring_requires_fused_composition():
    """monitor=/failures= on a composition without the fused loop (or
    with a size-mismatched monitor) must fail loudly at construction,
    not silently never migrate."""
    g = _graph()
    prog = sssp_bf(g)
    sched = plug.FailureSchedule(kills=[(1, 0)])
    with pytest.raises(ValueError, match="fused"):
        plug.Middleware(g, prog, daemon="reference", upper="host",
                        num_shards=2, failures=sched,
                        options=plug.PlugOptions(block_size=BLOCK))
    with pytest.raises(ValueError, match="fused"):
        plug.Middleware(g, prog, daemon="sharded", upper="host",
                        num_shards=2, failures=sched,
                        options=plug.PlugOptions(block_size=BLOCK))
    with pytest.raises(ValueError, match="monitor tracks"):
        plug.Middleware(g, prog, daemon="sharded", upper="mesh",
                        num_shards=SHARDS,
                        monitor=fault.FleetMonitor(num_hosts=3),
                        options=plug.PlugOptions(block_size=BLOCK))


def test_remesh_rejects_bad_survivor_mesh():
    """MeshUpperSystem.remesh validates the survivor mesh: the merge
    axis must exist and the stacked shard count must stay divisible."""
    import jax

    g = _graph()
    prog = sssp_bf(g)
    upper = plug.MeshUpperSystem()
    upper.bind(prog, 8)
    devs = np.asarray(jax.devices()[:1], dtype=object)
    with pytest.raises(ValueError, match="lacks the merge axis"):
        upper.remesh(jax.sharding.Mesh(devs, ("other",)))
    if len(jax.devices()) >= 3:
        devs3 = np.asarray(jax.devices()[:3], dtype=object)
        with pytest.raises(ValueError, match="not divisible"):
            upper.remesh(jax.sharding.Mesh(devs3, (upper.axis,)))


# --------------------------------------------------------------------------
# regression: dead hosts' busy-time samples must not reach survivors
# --------------------------------------------------------------------------
def test_monitor_drops_dead_host_samples():
    """Regression (satellite): a dead host's recorded step times are
    dropped at mark_failed — batch_fractions/stragglers/mean_times see
    survivors only, even when the dead host dominated the window."""
    mon = fault.FleetMonitor(num_hosts=3, model_parallel=1)
    for _ in range(4):
        mon.record(0, 50.0)  # soon-to-die outlier
        mon.record(1, 1.0)
        mon.record(2, 1.0)
    assert mon.stragglers()[0]  # alive, it IS a straggler
    mon.mark_failed(0)
    assert len(mon._times[0]) == 0  # window cleared, not just masked
    assert np.isnan(mon.mean_times()[0])
    np.testing.assert_allclose(mon.batch_fractions(), [0.0, 0.5, 0.5])
    assert not mon.stragglers().any()
    assert mon.observed  # survivors still report


def test_monitor_capacity_drift_tracking():
    """FleetMonitor drift primitives: drift is 0 before any ack, tracks
    the max relative per-host fraction change after one, and re-acking
    absorbs the current view."""
    mon = fault.FleetMonitor(num_hosts=4, drift_threshold=0.5)
    assert mon.capacity_drift() == 0.0 and not mon.drifted()
    for d in range(4):
        mon.record(d, 1.0)
    mon.ack_capacity()
    assert mon.capacity_drift() == 0.0  # view unchanged since ack
    mon.record(3, 20.0)  # host 3 degrades: its fraction collapses
    assert mon.capacity_drift() > 0.5
    assert mon.drifted()
    mon.ack_capacity()  # placement absorbed the degraded view
    assert mon.capacity_drift() == 0.0 and not mon.drifted()


def test_rebalance_after_migration_uses_survivor_capacities_only():
    """Regression (satellite): after a fault-injected migration,
    Middleware.rebalance() sources costs from the CURRENT mesh's
    survivors — the dead device's (extreme) pre-kill samples must not
    skew the Lemma-2 fractions — and the stale per-shard busy-time
    estimator is restarted rather than mixed in."""
    g = _graph()
    prog = sssp_bf(g)
    # reports land at the SAME poll as the kill: device 2's outlier
    # samples are recorded first, then the kill drops them — exactly the
    # mixing scenario the regression pins
    slow = [(2, d, 100.0 if d == 2 else 1.0) for d in range(SHARDS)]
    mw = _elastic(prog, g, kills=[(2, 2)], slow=slow)
    res = mw.run(max_iterations=40)
    assert len(_migrations(res)) == 1
    assert not mw._estimator.observed  # restarted at migration
    fr = mw.rebalance()  # costs from the monitor's survivor view
    assert fr.shape == (SHARDS,)
    # all survivors reported 1.0s: fractions are uniform; the dead
    # device's 100.0s samples leaking in would crater some fraction
    np.testing.assert_allclose(fr, np.full(SHARDS, 1.0 / SHARDS))
    res2 = mw.run(max_iterations=40)
    ref, _ = plug.run_reference(g, prog, max_iterations=40)
    np.testing.assert_array_equal(ref, res2.state)


def test_rebalance_without_any_observation_still_raises():
    """An elastic middleware whose monitor never saw a report keeps the
    strict rebalance contract (no silent uniform repartition)."""
    g = _graph()
    prog = sssp_bf(g)
    mw = plug.Middleware(g, prog, daemon="sharded", upper="mesh",
                         num_shards=SHARDS,
                         monitor=fault.FleetMonitor(num_hosts=SHARDS),
                         options=plug.PlugOptions(block_size=BLOCK))
    with pytest.raises(ValueError, match="busy times"):
        mw.rebalance()
