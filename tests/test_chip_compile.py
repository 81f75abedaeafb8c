"""The graph daemon's Pallas kernels compile for a TPU v5e chip.

The chip is described, not attached: ``topologies.get_topology_desc``
gives the TPU compiler a v5e:2x2 target, so Mosaic refuses here what it
would refuse on the chip (block shapes, layouts, unsupported ops).
Nothing runs.  The topology is described inside a module fixture — never
while a module is imported — and every test of this file skips where it
cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.graph import generate
from repro.graph.algorithms import pagerank, sssp_bf
from repro.kernels import ops
from repro.kernels.edge_block import csr_tile_pallas

# Graph500 SCALE-22 tiles: 512 edges, row and src blocks as wide as the
# edge tile; T is cut to 1024 tiles (the kernel body does not depend on T)
T, ET, RT, ST = 1024, 512, 512, 512
N = 1 << 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _program(monoid: str):
    g = generate.rmat(64, 256, seed=0)
    return {"sum": pagerank(g), "min": sssp_bf(g)}[monoid]


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("gather", ["take", "onehot"])
@pytest.mark.parametrize("monoid", ["sum", "min"])
def test_csr_tile_kernel_compiles_for_v5e(one_chip, monoid, gather):
    prog = _program(monoid)
    k, a = prog.state_width, max(prog.aux_width, 1)
    width = ST if gather == "onehot" else ET
    rows = RT if gather == "onehot" else ET

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (s((T, k, width)), s((T, a, width)), s((T, k, rows)),
            s((T, 1, ET), jnp.int32), s((T, 1, ET), jnp.int32),
            s((T, 1, ET)), s((T, 1, ET)))
    text = _compiled_text(
        lambda *xs: csr_tile_pallas(*xs, row_tile=RT, program=prog,
                                    gather=gather, interpret=False), args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("gather", ["take", "onehot"])
@pytest.mark.parametrize("monoid", ["sum", "min"])
def test_csr_tile_kernel_without_aux_and_row_compiles_for_v5e(
        one_chip, monoid, gather):
    """The kernel as a program that reads neither its aux nor its dst
    state gets it: src and the per-edge vectors only."""
    prog = _program(monoid)
    width = ST if gather == "onehot" else ET

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (s((T, prog.state_width, width)), s((T, 1, ET), jnp.int32),
            s((T, 1, ET), jnp.int32), s((T, 1, ET)), s((T, 1, ET)))
    text = _compiled_text(
        lambda src, *edges: csr_tile_pallas(
            src, None, None, *edges, row_tile=RT, program=prog,
            gather=gather, interpret=False), args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("gather", ["take", "onehot"])
@pytest.mark.parametrize("monoid", ["sum", "min"])
def test_csr_tile_kernel_with_an_activity_row_compiles_for_v5e(
        one_chip, monoid, gather):
    """The kernel of a frontier step: the src operand carries the
    source's activity as row K, split off inside the kernel."""
    prog = _program(monoid)
    width = ST if gather == "onehot" else ET

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (s((T, prog.state_width + 1, width)), s((T, 1, ET), jnp.int32),
            s((T, 1, ET), jnp.int32), s((T, 1, ET)), s((T, 1, ET)))
    text = _compiled_text(
        lambda src, *edges: csr_tile_pallas(
            src, None, None, *edges, row_tile=RT, program=prog,
            gather=gather, interpret=False, frontier=True), args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("monoid", ["sum", "min"])
def test_csr_aggregate_compiles_for_v5e(one_chip, monkeypatch, monoid):
    """The daemon's whole tile aggregate — K-major gathers, the kernel,
    the cross-tile combine — as the fused step traces it on a TPU."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    prog = _program(monoid)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    csr = {"rows": s((T, RT)), "seg": s((T, ET)), "lsrc": s((T, ET)),
           "svids": s((T, ST)), "w": s((T, ET), jnp.float32),
           "emask": s((T, ET), jnp.bool_), "gsrc": s((T, ET)),
           "gdst": s((T, ET))}
    args = (s((N, prog.state_width), jnp.float32),
            s((N, 1), jnp.float32), csr)
    text = _compiled_text(
        lambda st, ax, c: ops.csr_aggregate(st, ax, c, program=prog,
                                            num_vertices=N),
        args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("monoid", ["sum", "min"])
def test_csr_aggregate_with_a_frontier_compiles_for_v5e(one_chip,
                                                        monkeypatch, monoid):
    """The frontier step's tile aggregate: the activity gathered with the
    src state in one pass, the kernel's tile counts read back."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    prog = _program(monoid)

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    csr = {"rows": s((T, RT)), "seg": s((T, ET)), "lsrc": s((T, ET)),
           "svids": s((T, ST)), "w": s((T, ET), jnp.float32),
           "emask": s((T, ET), jnp.bool_), "gsrc": s((T, ET)),
           "gdst": s((T, ET))}
    args = (s((N, prog.state_width), jnp.float32),
            s((N, 1), jnp.float32), csr, s((N,), jnp.bool_))
    text = _compiled_text(
        lambda st, ax, c, act: ops.csr_aggregate(
            st, ax, c, program=prog, num_vertices=N, active=act),
        args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("monoid", ["sum", "min"])
def test_edge_block_kernel_compiles_for_v5e(one_chip, monkeypatch, monoid):
    """The block-layout caller of the same tile kernel (the streaming
    daemons' ``kernel="pallas"``) at 512-edge blocks."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    prog = _program(monoid)
    nb, b, vb = 256, 512, 512

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (s((N, prog.state_width), jnp.float32), s((N, 1), jnp.float32),
            s((nb, vb)), s((nb, b)), s((nb, b)), s((nb, b, 1), jnp.float32),
            s((nb, b), jnp.bool_))
    text = _compiled_text(
        lambda *xs: ops.edge_block_aggregate(*xs, program=prog), args)
    assert "tpu_custom_call" in text
