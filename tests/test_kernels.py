"""Per-kernel validation: Pallas kernels (interpret mode on CPU) vs ref.py
oracle, swept over shapes — exact integer equality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.deas_gemm import deas_gemm
from repro.kernels.ops import int8_gemm
from repro.kernels.ref import ref_int8_gemm, ref_spoga_gemm
from repro.kernels.spoga_gemm import spoga_gemm


def _rand_int8(key, shape):
    return jax.random.randint(key, shape, -128, 128, dtype=jnp.int8)


SHAPES = [
    (8, 16, 8),        # tiny
    (128, 128, 128),   # single tile
    (256, 512, 256),   # exact default tiles
    (130, 257, 100),   # ragged -> padding path
    (1, 249, 16),      # the paper's DPU shape: N=249 vector, M=16 dot products
    (512, 1024, 256),  # multi-tile K loop
]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_spoga_kernel_matches_oracle(m, k, n):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n))
    x, w = _rand_int8(kx, (m, k)), _rand_int8(kw, (k, n))
    got = spoga_gemm(x, w, block_m=128, block_n=128, block_k=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_int8_gemm(x, w)))


@pytest.mark.parametrize("m,k,n", SHAPES[:4])
def test_deas_kernel_matches_oracle(m, k, n):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 7 + k * 3 + n))
    x, w = _rand_int8(kx, (m, k)), _rand_int8(kw, (k, n))
    got = deas_gemm(x, w, block_m=128, block_n=128, block_k=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_int8_gemm(x, w)))


@pytest.mark.parametrize("bm,bn,bk", [(64, 64, 64), (128, 256, 128), (256, 128, 512)])
def test_spoga_kernel_block_shape_sweep(bm, bn, bk):
    kx, kw = jax.random.split(jax.random.PRNGKey(bm + bn + bk))
    x, w = _rand_int8(kx, (192, 320)), _rand_int8(kw, (320, 160))
    got = spoga_gemm(x, w, block_m=bm, block_n=bn, block_k=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_int8_gemm(x, w)))


def test_ref_spoga_equals_ref_direct():
    kx, kw = jax.random.split(jax.random.PRNGKey(42))
    x, w = _rand_int8(kx, (64, 96)), _rand_int8(kw, (96, 32))
    np.testing.assert_array_equal(
        np.asarray(ref_spoga_gemm(x, w)), np.asarray(ref_int8_gemm(x, w))
    )


@pytest.mark.parametrize("mode", ["int8_spoga", "int8_deas", "int8_direct"])
def test_ops_dispatch(mode):
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    x, w = _rand_int8(kx, (32, 64)), _rand_int8(kw, (64, 16))
    got = int8_gemm(x, w, mode=mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_int8_gemm(x, w)))


def test_ops_dispatch_interpret_kernel():
    kx, kw = jax.random.split(jax.random.PRNGKey(4))
    x, w = _rand_int8(kx, (256, 256)), _rand_int8(kw, (256, 256))
    got = int8_gemm(x, w, mode="int8_spoga", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_int8_gemm(x, w)))


class TestSpogaGemmDequant:
    """Fused W8A8 + epilogue kernel vs the pure-jnp oracle."""

    @pytest.mark.parametrize("m,k,n", [(32, 64, 32), (48, 160, 96), (128, 512, 256)])
    def test_matches_oracle(self, m, k, n):
        from repro.kernels.ref import ref_spoga_gemm_dequant
        from repro.kernels.spoga_gemm_dequant import spoga_gemm_dequant

        rng = np.random.default_rng(m * k + n)
        x = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
        w = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
        xs = jnp.asarray(rng.uniform(1e-3, 0.1, (m, 1)).astype(np.float32))
        ws = jnp.asarray(rng.uniform(1e-3, 0.1, (1, n)).astype(np.float32))
        got = spoga_gemm_dequant(x, w, xs, ws, block_m=32, block_n=32,
                                 block_k=64, interpret=True)
        want = ref_spoga_gemm_dequant(x, w, xs, ws)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    def test_padding_path(self):
        from repro.kernels.ref import ref_spoga_gemm_dequant
        from repro.kernels.spoga_gemm_dequant import spoga_gemm_dequant

        rng = np.random.default_rng(7)
        m, k, n = 33, 70, 45  # none divide the block sizes
        x = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
        w = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
        xs = jnp.ones((m, 1), jnp.float32) * 0.02
        ws = jnp.ones((1, n), jnp.float32) * 0.05
        got = spoga_gemm_dequant(x, w, xs, ws, block_m=32, block_n=32,
                                 block_k=64, interpret=True)
        want = ref_spoga_gemm_dequant(x, w, xs, ws)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# -- paged attention on stacked, lane-dense pools ---------------------------

@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_paged_attention_reads_each_layer_of_a_stacked_pool(int8, head_dim):
    """The kernel on a stack of layers' pools, told a layer, equals the
    reference on that layer's pages alone — for every layer, over ragged
    lengths with idle (length-1) lanes among them."""
    from repro.kernels.paged_attention import paged_attention, paged_attention_ref

    rng = np.random.default_rng(head_dim + int8)
    n_layers, hkv, g, ps, n_tbl = 3, 2, 3, 16, 19
    lengths = jnp.asarray([1, 129, 300, 1, n_tbl * ps, 17], jnp.int32)
    b = lengths.shape[0]
    n_pages = b * n_tbl + 1
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(b, n_tbl),
                         jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, hkv, g, head_dim)), jnp.float32)
    kv = (n_layers, n_pages, ps, hkv * head_dim)
    if int8:
        kp, vp = (jnp.asarray(rng.integers(-127, 128, kv), jnp.int8) for _ in "kv")
        pools = dict(scales=jnp.asarray(
            rng.uniform(0.005, 0.02, (n_layers, n_pages, 2, hkv * ps)), jnp.float32))
    else:
        kp, vp = (jnp.asarray(rng.normal(size=kv), jnp.bfloat16) for _ in "kv")
        pools = {}
    outs = []
    for layer in range(n_layers):
        one = {k: v[layer][None] for k, v in pools.items()}
        want = paged_attention_ref(q, kp[layer][None], vp[layer][None], tables,
                                   lengths, 0, **one)
        got = paged_attention(q, kp, vp, tables, lengths, jnp.int32(layer),
                              interpret=True, **pools)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        outs.append(np.asarray(got))
    # the layers hold different pages, so a kernel reading the wrong one fails
    assert not np.allclose(outs[0], outs[1])
