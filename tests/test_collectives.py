"""Gradient-compression collective: int8 psum == fp32 psum within quant error."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.runtime.collectives import compressed_psum_mean, psum_mean

pytestmark = pytest.mark.skipif(
    jax.device_count() < 1, reason="needs at least one device")


def _run_shardmap(fn, n_dev, *args):
    mesh = jax.make_mesh((n_dev,), ("data",))
    sharded = shard_map(
        fn, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    return sharded(*args)


@pytest.mark.parametrize("shape", [(4, 8), (3, 5, 7)])
def test_compressed_matches_exact_within_quant_error(shape):
    n_dev = jax.device_count()
    key = jax.random.PRNGKey(0)
    # per-shard gradients with heterogeneous magnitude
    g = jax.random.normal(key, (n_dev,) + shape, jnp.float32) * 0.3

    exact = _run_shardmap(
        functools.partial(psum_mean, axis_name="data"), n_dev, g)
    comp = _run_shardmap(
        functools.partial(compressed_psum_mean, axis_name="data"), n_dev, g)

    # error bound: one int8 step of the agreed global scale per shard
    step = float(jnp.max(jnp.abs(g))) / 127.0
    np.testing.assert_allclose(
        np.asarray(comp), np.asarray(exact), atol=step + 1e-7)


def test_compression_is_deterministic():
    n_dev = jax.device_count()
    g = jax.random.normal(jax.random.PRNGKey(1), (n_dev, 16), jnp.float32)
    a = _run_shardmap(
        functools.partial(compressed_psum_mean, axis_name="data"), n_dev, g)
    b = _run_shardmap(
        functools.partial(compressed_psum_mean, axis_name="data"), n_dev, g)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_gradients_stay_zero():
    n_dev = jax.device_count()
    g = jnp.zeros((n_dev, 8), jnp.float32)
    out = _run_shardmap(
        functools.partial(compressed_psum_mean, axis_name="data"), n_dev, g)
    np.testing.assert_array_equal(np.asarray(out), 0.0)
