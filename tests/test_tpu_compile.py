"""The main-path Pallas kernels compile for a TPU v5e — checked here, on CPU.

JAX's TPU compiler compiles for a chip that is described and not attached
(``jax.experimental.topologies``), so what Mosaic would refuse on the chip
(an unsupported op, a block shape off the (8, 128) tiling, a kernel that
cannot be partitioned) fails here first.  Nothing runs: results are tested
against the interpret twins elsewhere and on the chip by ``chip_smoke.py``.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.backends import resolve_backend
from repro.configs import get_config
from repro.kernels.deas_gemm import deas_gemm
from repro.kernels.paged_attention import paged_attention
from repro.kernels.spoga_gemm import spoga_gemm
from repro.kernels.spoga_gemm_dequant import spoga_gemm_dequant
from repro.shard.kernels import sharded_gemm_dequant, sharded_paged_attention

LLAMA = get_config("llama3.2-1b")
D, FF, VOCAB = LLAMA.d_model, LLAMA.d_ff, LLAMA.vocab_size
LANES, PROMPT = 8, 512
# decode (one row per lane), prefill (one bucket-sized prompt), LM head
GEMM_SHAPES = {"decode": (LANES, D, FF), "prefill": (PROMPT, FF, D),
               "lm_head": (LANES, D, VOCAB)}
# the odd / non-tile-multiple shapes of tests/test_backends.py: every
# padding path of the compiled kernels
ODD_SHAPES = [(8, 16, 8), (33, 70, 45), (1, 249, 16), (130, 257, 100)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


@pytest.fixture
def on_tpu(monkeypatch):
    """The compiled registry names raise unless JAX's default backend is a
    TPU; these compiles target one that is described, not attached."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _gemm_args(sharding, m, k, n):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return (s((m, k), jnp.int8), s((k, n), jnp.int8),
            s((m, 1), jnp.float32), s((1, n), jnp.float32))


@pytest.mark.parametrize("w_slices", [2, 1], ids=["w8a8", "w4a8"])
@pytest.mark.parametrize("shape", sorted(GEMM_SHAPES))
def test_spoga_gemm_dequant_compiles(one_chip, shape, w_slices):
    hlo = _compile(lambda *a: spoga_gemm_dequant(*a, n_w_slices=w_slices),
                   *_gemm_args(one_chip, *GEMM_SHAPES[shape]))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", [spoga_gemm, deas_gemm],
                         ids=["spoga_gemm", "deas_gemm"])
def test_int32_gemm_kernels_compile(one_chip, kernel):
    x, w, _, _ = _gemm_args(one_chip, *GEMM_SHAPES["prefill"])
    assert "tpu_custom_call" in _compile(kernel, x, w)


@pytest.mark.parametrize("name", ["pallas_spoga", "pallas_spoga_dequant",
                                  "pallas_deas"])
@pytest.mark.parametrize("m,k,n", ODD_SHAPES)
def test_compiled_backend_compiles(one_chip, on_tpu, name, m, k, n):
    """The registry's compiled names at the shapes their CPU exactness
    cases (tests/test_backends.py) now run through the interpret twins."""
    b, spec = resolve_backend("int8_spoga", name)
    x, w, xs, ws = _gemm_args(one_chip, m, k, n)
    if b.gemm_dequant is not None:
        hlo = _compile(lambda *a: b.gemm_dequant(*a, spec), x, w, xs, ws)
    else:
        hlo = _compile(lambda x, w: b.gemm(x, w, spec), x, w)
    assert "tpu_custom_call" in hlo


def _paged_args(sharding, int8, lanes=LANES, pages_per_lane=36, geometry=None):
    """Paged-attention operands: llama3.2-1b's heads by default, else
    ``geometry`` = (H_kv, G, D) over a pool of 4097 pages."""
    if geometry is None:
        hkv, d = LLAMA.n_kv_heads, LLAMA.resolved_head_dim
        g = LLAMA.n_heads // hkv
        n_pages = lanes * pages_per_lane + 1
    else:
        (hkv, g, d), n_pages = geometry, 4097
    ps = 16
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    dt = jnp.int8 if int8 else jnp.bfloat16
    args = [s((lanes, hkv, g, d), jnp.bfloat16), s((n_pages, ps, hkv, d), dt),
            s((n_pages, ps, hkv, d), dt), s((lanes, pages_per_lane), jnp.int32),
            s((lanes,), jnp.int32)]
    if int8:
        args += [s((n_pages, ps, hkv), jnp.float32)] * 2
    return args


def _paged(attn):
    def call(q, kp, vp, tables, lengths, *scales):
        ks, vs = scales if scales else (None, None)
        return attn(q, kp, vp, tables, lengths, k_scale=ks, v_scale=vs)
    return call


# the served geometry: 32 lanes of 257-page tables (a table that is not a
# multiple of the block), H_kv 8 with D 128, G 4 (mistral-nemo-12b) and
# D 64, G 3 (granite-3.0-3b-a800m)
SERVED = {"nemo-int8": (8, 4, 128), "granite-int8": (8, 3, 64)}


@pytest.mark.parametrize("int8, geometry", [
    (False, None), (True, None), *[(True, g) for g in SERVED.values()]],
    ids=["bf16", "int8", *SERVED])
def test_paged_attention_compiles(one_chip, int8, geometry):
    kw = {} if geometry is None else dict(lanes=32, pages_per_lane=257,
                                          geometry=geometry)
    hlo = _compile(_paged(paged_attention), *_paged_args(one_chip, int8, **kw))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("layout", ["col", "row"])
def test_sharded_gemm_compiles_on_2x2(tp4, on_tpu, layout):
    """Under a 4-way "model" mesh the kernel runs inside shard_map: a
    column-parallel GEMM needs no collective, a row-parallel one psums its
    int32 partials (one all-reduce) before the epilogue."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    b, spec = resolve_backend("int8_spoga", "pallas_spoga_dequant")
    m, k, n = GEMM_SHAPES["prefill"]
    w_spec = P(None, "model") if layout == "col" else P("model", None)
    args = list(_gemm_args(NamedSharding(tp4, P()), m, k, n))
    args[1] = jax.ShapeDtypeStruct((k, n), jnp.int8,
                                   sharding=NamedSharding(tp4, w_spec))
    hlo = _compile(lambda *a: sharded_gemm_dequant(b, *a, spec, tp4, layout),
                   *args)
    assert "tpu_custom_call" in hlo
    assert ("all-reduce" in hlo) == (layout == "row")
    assert "all-gather" not in hlo  # no weight ever gathered


def test_sharded_paged_attention_compiles_on_2x2(tp4):
    """Each device attends over its own KV heads of its own pool shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    args = _paged_args(NamedSharding(tp4, P()), int8=True)
    heads = {0: P(None, "model"), 1: P(None, None, "model"),
             2: P(None, None, "model"), 5: P(None, None, "model"),
             6: P(None, None, "model")}
    for i, spec in heads.items():
        args[i] = jax.ShapeDtypeStruct(args[i].shape, args[i].dtype,
                                       sharding=NamedSharding(tp4, spec))
    with tp4:
        hlo = _compile(_paged(sharded_paged_attention), *args)
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo and "all-reduce" not in hlo
