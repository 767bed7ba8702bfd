"""The GEMM backend registry: every backend is the same integer arithmetic.

Covers the tentpole refactor's guarantees without optional deps:

* every backend that runs on CPU == ``direct_matmul`` bit-exactly, over
  odd / non-tile-multiple shapes (exercising the padded-slice path of the
  fused ``spoga_gemm_dequant`` kernel through ``pallas_interpret``); the
  compiled Pallas names raise off-TPU;
* ``slice_planes`` round-trips for all (n_slices, slice_bits) combos
  including the extremes (-128, int16 min);
* the ``w4a8`` / ``w4a4`` / ``w16a16`` parametric modes run end-to-end and
  ``w4a8`` is bit-exact against a hand-built jnp reference;
* ``models.layers.linear`` routes through the registry (no local dispatch).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import (
    QuantSpec,
    dynamic_quant,
    get_backend,
    list_backends,
    parse_quant_mode,
    quantized_linear,
    resolve_backend,
    set_default_backend,
)
from repro.core.slicing import reconstruct_planes, slice_planes
from repro.core.spoga import direct_matmul, sliced_matmul

# The compiled Pallas names run only on a TPU (their shapes are compile
# cases in tests/test_tpu_compile.py); on CPU the kernel bodies run through
# the named interpret twins.
COMPILED_BACKENDS = {"pallas_spoga", "pallas_spoga_dequant", "pallas_deas"}
CPU_BACKENDS = {
    "jnp_spoga", "jnp_deas", "direct",
    "pallas_interpret", "pallas_deas_interpret",
}
EXPECTED_BACKENDS = COMPILED_BACKENDS | CPU_BACKENDS

# Odd / non-tile-multiple shapes: every padding path in the kernels fires.
SHAPES = [(8, 16, 8), (33, 70, 45), (1, 249, 16), (130, 257, 100)]


def _rand_int8(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, -128, 128,
                              dtype=jnp.int8)


# ---------------------------------------------------------------------------
# Registry surface
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_builtin_backends_registered(self):
        assert EXPECTED_BACKENDS <= set(list_backends())

    def test_get_backend_unknown_raises(self):
        with pytest.raises(KeyError, match="unknown GEMM backend"):
            get_backend("definitely_not_a_backend")

    def test_resolve_auto_is_jnp_off_tpu(self):
        if jax.default_backend() == "tpu":
            pytest.skip("auto-selection picks the Pallas kernels on TPU")
        b, spec = resolve_backend("int8_spoga")
        assert b.name == "jnp_spoga"
        assert (spec.n_a_slices, spec.n_w_slices, spec.slice_bits) == (2, 2, 4)

    def test_resolve_respects_override_and_default(self):
        b, _ = resolve_backend("int8_spoga", "pallas_interpret")
        assert b.name == "pallas_interpret"
        set_default_backend("direct")
        try:
            b, _ = resolve_backend("int8_spoga")
            assert b.name == "direct"
        finally:
            set_default_backend(None)

    def test_unsupported_spec_rejected(self):
        # The Pallas DEAS baseline is pinned to the paper's W8A8 2x4b spec.
        with pytest.raises(ValueError, match="does not support"):
            resolve_backend("w4a8", "pallas_deas")

    def test_parse_quant_mode(self):
        spec, family = parse_quant_mode("w4a8")
        assert (spec.w_bits, spec.a_bits, family) == (4, 8, "spoga")
        assert (spec.n_w_slices, spec.n_a_slices) == (1, 2)
        spec, _ = parse_quant_mode("w8a8_s2")
        assert (spec.slice_bits, spec.n_a_slices) == (2, 4)
        with pytest.raises(ValueError):
            parse_quant_mode("bf16")
        with pytest.raises(ValueError):
            parse_quant_mode("int7_nonsense")


# ---------------------------------------------------------------------------
# Exactness: every backend vs the native direct GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CPU_BACKENDS))
@pytest.mark.parametrize("m,k,n", SHAPES)
def test_backend_exact_vs_direct(name, m, k, n):
    x, w = _rand_int8(m * k + n, (m, k)), _rand_int8(k * n + m, (k, n))
    b, spec = resolve_backend("int8_spoga", name)
    got = b.gemm(x, w, spec)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(direct_matmul(x, w)))


@pytest.mark.parametrize("name", sorted(COMPILED_BACKENDS))
def test_compiled_backend_raises_off_tpu(name):
    """A compiled Pallas name never falls back to the interpreter: off-TPU
    it raises, naming the interpret twin to use instead."""
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled kernels run natively on TPU")
    b, spec = resolve_backend("int8_spoga", name)
    x, w = _rand_int8(0, (8, 16)), _rand_int8(1, (16, 8))
    with pytest.raises(RuntimeError, match="_interpret"):
        b.gemm(x, w, spec)


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_fused_dequant_padded_slice_path(m, k, n):
    """pallas_interpret's gemm_dequant (the fused TPU kernel body) on CPU,
    over shapes that force the zero-padding path, vs the jnp epilogue."""
    rng = np.random.default_rng(m + k + n)
    x, w = _rand_int8(m + 1, (m, k)), _rand_int8(n + 2, (k, n))
    xs = jnp.asarray(rng.uniform(1e-3, 0.1, (m, 1)).astype(np.float32))
    ws = jnp.asarray(rng.uniform(1e-3, 0.1, (1, n)).astype(np.float32))
    b, spec = resolve_backend("int8_spoga", "pallas_interpret")
    got = b.gemm_dequant(x, w, xs, ws, spec)
    want = direct_matmul(x, w).astype(jnp.float32) * xs * ws
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("mode", ["w4a8", "w4a4", "w16a16", "w8a8_s2"])
@pytest.mark.parametrize("backend", ["jnp_spoga", "jnp_deas", "pallas_interpret"])
def test_parametric_modes_bitexact_across_backends(mode, backend):
    """All backends produce IDENTICAL integers for every parametric spec
    (int32 accumulation wraps identically everywhere, so this holds even
    for w16a16's mod-2^32 regime)."""
    spec, _ = parse_quant_mode(mode)
    if backend == "pallas_interpret" and spec.slice_bits > 7:
        pytest.skip("Pallas planes ride the MXU byte path")
    rng = np.random.default_rng(hash(mode) % 2**32)
    qa = int(spec.a_qmax)
    qw = int(spec.w_qmax)
    x = jnp.asarray(rng.integers(-qa, qa + 1, (19, 37)), spec.a_dtype)
    w = jnp.asarray(rng.integers(-qw, qw + 1, (37, 11)), spec.w_dtype)
    b, spec = resolve_backend(mode, backend)
    got = b.gemm(x, w, spec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(direct_matmul(x, w)))


# ---------------------------------------------------------------------------
# slice_planes: exact round-trip for every plane plan
# ---------------------------------------------------------------------------

class TestSlicePlanes:
    CASES = [
        # (dtype, n_slices, slice_bits)
        (jnp.int8, 2, 4),    # the paper's MSN/LSN
        (jnp.int8, 4, 2),    # SCONNA-style narrow slices
        (jnp.int8, 8, 1),    # bit-serial extreme
        (jnp.int8, 1, 8),    # degenerate single plane
        (jnp.int16, 4, 4),   # int16 on nibble hardware
        (jnp.int16, 2, 8),   # int16 on byte hardware
        (jnp.int16, 8, 2),
    ]

    @pytest.mark.parametrize("dtype,n,b", CASES)
    def test_roundtrip_exhaustive_or_extreme(self, dtype, n, b):
        if dtype == jnp.int8:
            x = jnp.arange(-128, 128, dtype=jnp.int8)  # all of int8
        else:
            vals = np.r_[np.array([-32768, -32767, -1, 0, 1, 32766, 32767]),
                         np.random.default_rng(0).integers(-32768, 32768, 512)]
            x = jnp.asarray(vals, jnp.int16)
        planes = slice_planes(x, n, b)
        assert len(planes) == n
        np.testing.assert_array_equal(
            np.asarray(reconstruct_planes(planes, b, dtype)), np.asarray(x))

    def test_plane_ranges(self):
        x = jnp.arange(-128, 128, dtype=jnp.int8)
        lo, hi = slice_planes(x, 2, 4)
        assert int(lo.min()) >= 0 and int(lo.max()) <= 15      # unsigned digit
        assert int(hi.min()) >= -8 and int(hi.max()) <= 7      # signed top

    def test_int4_passthrough(self):
        """1-plane slicing of int4-in-int8 is the identity."""
        x = jnp.arange(-8, 8, dtype=jnp.int8)
        (plane,) = slice_planes(x, 1, 4)
        np.testing.assert_array_equal(np.asarray(plane), np.asarray(x))

    def test_rejects_bad_args(self):
        with pytest.raises(TypeError):
            slice_planes(jnp.zeros((4,), jnp.float32), 2, 4)
        with pytest.raises(ValueError):
            slice_planes(jnp.zeros((4,), jnp.int8), 0, 4)

    @pytest.mark.parametrize("nx,nw,b", [(2, 2, 4), (4, 4, 2), (2, 1, 4), (1, 2, 4)])
    def test_sliced_matmul_matches_direct(self, nx, nw, b):
        x, w = _rand_int8(nx * 10 + nw, (23, 31)), _rand_int8(b, (31, 17))
        got = sliced_matmul(x, w, n_x_slices=nx, n_w_slices=nw, slice_bits=b)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(direct_matmul(x, w)))


# ---------------------------------------------------------------------------
# The quantized-linear pipeline + model hot path
# ---------------------------------------------------------------------------

class TestQuantizedLinearPipeline:
    def _data(self, lead=(6,), k=64, n=32, seed=3):
        kx, kw = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(kx, (*lead, k), jnp.float32)
        w = jax.random.normal(kw, (k, n), jnp.float32) * 0.1
        return x, w

    def test_w4a8_bitexact_vs_jnp_reference(self):
        """The acceptance bar: w4a8 through the registry == a hand-built
        quantize/slice/accumulate/dequant reference, bit for bit."""
        x, w = self._data()
        for backend in ("jnp_spoga", "pallas_interpret"):
            got = quantized_linear(x, w, "w4a8", backend=backend,
                                   out_dtype=jnp.float32)
            # reference: int8 row-quant acts, int4 col-quant weights,
            # 2x1-plane radix GEMM, f32 epilogue — all in plain jnp.
            xq, xs = dynamic_quant(x, axis=-1, bits=8)
            wq, ws = dynamic_quant(w, axis=0, bits=4)
            acc = sliced_matmul(xq, wq, n_x_slices=2, n_w_slices=1, slice_bits=4)
            want = acc.astype(jnp.float32) * xs * ws
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("mode,tol", [
        ("int8_spoga", 0.02), ("w4a8", 0.2), ("w4a4", 0.3), ("w8a8_s2", 0.02),
    ])
    def test_pipeline_accuracy(self, mode, tol):
        x, w = self._data(lead=(4, 8))
        y = quantized_linear(x, w, mode, out_dtype=jnp.float32)
        exact = jnp.einsum("...k,kn->...n", x, w)
        rel = float(jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact))
        assert rel < tol, f"{mode}: rel err {rel}"

    def test_w16a16_narrow_k_accuracy(self):
        """int16 operands stay inside the int32 accumulator for narrow K."""
        x, w = self._data(lead=(5,), k=8, n=6)
        y = quantized_linear(x * 1e-2, w, "w16a16", out_dtype=jnp.float32)
        exact = (x * 1e-2) @ w
        rel = float(jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact))
        assert rel < 1e-3, rel  # 16-bit quantization error only

    def test_linear_routes_through_registry(self):
        """models.layers.linear defers to the registry for every quant mode
        (monkeypatch-free check: an explicit backend choice changes nothing
        numerically but must be accepted end-to-end, incl. the Pallas
        interpreter on CPU)."""
        from repro.models.layers import linear

        x, w = self._data(lead=(2, 5))
        y_auto = linear(x, w, "int8_spoga")
        y_interp = linear(x, w, "int8_spoga", "pallas_interpret")
        np.testing.assert_allclose(np.asarray(y_auto, dtype=np.float32),
                                   np.asarray(y_interp, dtype=np.float32),
                                   rtol=2e-2, atol=1e-6)
        y4 = linear(x, w, "w4a8")
        assert y4.shape == y_auto.shape

    def test_linear_no_string_dict_dispatch_in_source(self):
        """Regression guard for the refactor's core claim: the model layer
        carries no mode-string dict dispatch anymore."""
        import inspect

        import repro.models.layers as layers

        src = inspect.getsource(layers)
        assert "int8_spoga\":" not in src and "'int8_spoga':" not in src
        assert "quantized_linear" in src  # routes through the pipeline

    def test_ste_gradients_flow(self):
        x, w = self._data(lead=(7,))
        from repro.models.layers import linear

        def loss(w_):
            return jnp.sum(linear(x, w_, "w4a8", "pallas_interpret") ** 2)

        g = jax.grad(loss)(w)
        assert g.shape == w.shape
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))

    def test_moe_grouped_matmul_modes(self):
        """The grouped expert GEMM follows the same spec/family routing."""
        from repro.models.moe import _grouped_matmul

        kx, kw = jax.random.split(jax.random.PRNGKey(11))
        x = jax.random.normal(kx, (3, 4, 16), jnp.float32)   # (E, C, K)
        w = jax.random.normal(kw, (3, 16, 8), jnp.float32) * 0.1
        outs = {m: _grouped_matmul(x, w, m)
                for m in ("int8_spoga", "int8_deas", "int8_direct", "w4a8")}
        for m, o in outs.items():
            assert o.shape == (3, 4, 8), m
        # the three int8 dataflows agree bit-exactly
        np.testing.assert_array_equal(np.asarray(outs["int8_spoga"]),
                                      np.asarray(outs["int8_deas"]))
        np.testing.assert_array_equal(np.asarray(outs["int8_spoga"]),
                                      np.asarray(outs["int8_direct"]))


class TestConfigIntegration:
    def test_config_accepts_parametric_mode_and_backend(self):
        from repro.configs.base import ModelConfig

        cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=8,
                          n_heads=2, n_kv_heads=2, d_ff=16, vocab_size=32,
                          quant_mode="w4a8", gemm_backend="pallas_interpret")
        assert cfg.quant_mode == "w4a8"
        with pytest.raises(ValueError):
            ModelConfig(name="t", family="dense", n_layers=1, d_model=8,
                        n_heads=2, n_kv_heads=2, d_ff=16, vocab_size=32,
                        quant_mode="nope")
        with pytest.raises(KeyError):
            ModelConfig(name="t", family="dense", n_layers=1, d_model=8,
                        n_heads=2, n_kv_heads=2, d_ff=16, vocab_size=32,
                        gemm_backend="nope")

    def test_quant_spec_validation(self):
        with pytest.raises(ValueError):
            QuantSpec(a_bits=1)
        with pytest.raises(ValueError):
            QuantSpec(slice_bits=9)
