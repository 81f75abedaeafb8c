"""Sharding rules: divisibility fallback, spec construction, fault plans."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import fault, sharding as shd


@pytest.fixture(scope="module")
def mesh():
    n = len(jax.devices())
    return shd.make_mesh((1, n), ("data", "model"))


def test_spec_divisible(mesh):
    rules = shd.make_rules(mesh)
    n = mesh.shape["model"]
    spec = shd.spec_for((4 * n, 8), (shd.TENSOR, None), mesh, rules)
    assert spec == P("model")


def test_spec_fallback_replicates(mesh):
    rules = shd.make_rules(mesh)
    n = mesh.shape["model"]
    if n == 1:
        pytest.skip("single device: everything divides")
    spec = shd.spec_for((n + 1, 8), (shd.TENSOR, None), mesh, rules)
    assert spec == P()


def test_no_axis_used_twice(mesh):
    rules = shd.make_rules(mesh)
    n = mesh.shape["model"]
    spec = shd.spec_for((4 * n, 4 * n), (shd.TENSOR, shd.VOCAB), mesh, rules)
    flat = [a for part in spec for a in (part if isinstance(part, tuple)
                                         else (part,)) if part]
    assert len(flat) == len(set(flat))


def test_tree_shardings_structure(mesh):
    rules = shd.make_rules(mesh)
    tree = {"w": jax.ShapeDtypeStruct((8, 8), jax.numpy.float32)}
    axes = {"w": (shd.FSDP, shd.TENSOR)}
    out = shd.tree_shardings(tree, axes, mesh, rules)
    assert set(out) == {"w"}


def test_constrain_noop_without_context():
    x = jax.numpy.ones((4, 4))
    y = shd.constrain(x, (shd.BATCH, None))
    assert y is x


def test_constrain_applies_in_context(mesh):
    rules = shd.make_rules(mesh)

    def f(x):
        return shd.constrain(x, (None, shd.TENSOR)) * 2

    n = mesh.shape["model"]
    x = jax.numpy.ones((4, 4 * n))
    with mesh, shd.activation_sharding(mesh, rules):
        y = jax.jit(f)(x)
    np.testing.assert_array_equal(np.asarray(y), 2 * np.ones((4, 4 * n)))


# --------------------------------------------------------------------------
# fault tolerance plans
# --------------------------------------------------------------------------
def test_elastic_plan_shrinks_data_axis():
    plan = fault.elastic_plan(512, model_parallel=16)
    assert plan.shape == (2, 16, 16)
    plan = fault.elastic_plan(448, model_parallel=16)  # lost 4 hosts
    assert plan.size <= 448 and plan.shape[-1] == 16
    plan = fault.elastic_plan(16, model_parallel=16)
    assert plan.shape == (1, 16)


def test_elastic_plan_rejects_too_small():
    with pytest.raises(ValueError):
        fault.elastic_plan(8, model_parallel=16)


def test_fleet_monitor_stragglers_and_fractions():
    mon = fault.FleetMonitor(num_hosts=4, model_parallel=4)
    for _ in range(5):
        for h, t in enumerate([1.0, 1.0, 1.0, 3.0]):
            mon.record(h, t)
    strag = mon.stragglers()
    assert list(strag) == [False, False, False, True]
    frac = mon.batch_fractions()
    assert frac[3] < frac[0]
    assert frac.sum() == pytest.approx(1.0)
    mon.mark_failed(3)
    frac = mon.batch_fractions()
    assert frac[3] == 0.0
    assert frac.sum() == pytest.approx(1.0)


def test_detect_stragglers():
    t = np.array([1.0, 1.1, 0.9, 5.0])
    assert list(fault.detect_stragglers(t)) == [False, False, False, True]
