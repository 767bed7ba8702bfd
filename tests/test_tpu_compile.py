"""The main-path Pallas kernels compile for a TPU v5e — checked here, on CPU.

JAX's TPU compiler compiles for a chip that is described and not attached
(``jax.experimental.topologies``), so what Mosaic would refuse on the chip
(an unsupported op, a block shape off the (8, 128) tiling, a kernel that
cannot be partitioned) fails here first.  Nothing runs: results are tested
against the interpret twins elsewhere and on the chip by ``chip_smoke.py``.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.backends import resolve_backend
from repro.configs import get_config, reduced
from repro.configs.base import MLAConfig
from repro.kernels.deas_gemm import deas_gemm
from repro.kernels.paged_attention import paged_attention
from repro.kernels.spoga_gemm import spoga_gemm
from repro.kernels.spoga_gemm_dequant import spoga_gemm_dequant
from repro.models.model import param_shapes, paged_cache_shapes
from repro.serving.engine import _jitted_decode_sample
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


# ops that move a whole pool: a copy (a relayout among them), a slice of one
# layer's pool out of the stack and its write back, a transpose
_POOL_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
               "dynamic-update-slice", "transpose")


def _pool_ops(hlo: str, n_pages: int) -> list:
    """Instructions of a compiled program that move a page pool: one of
    ``_POOL_MOVES`` whose result has the pool's page count in its shape.  A
    pool small enough to fit on chip may be staged into the chip's memory
    (memory space ``S(1)``) and back by the compiler's memory-space
    assignment; such a staging copy, whose result or operand lies there, is
    not counted."""
    dim = re.compile(rf"[\[,]{n_pages}[,\]]")
    inst = re.compile(r"%(\S+) = (.*?) ([a-z][a-z-]*)\(([^)]*)\)")
    shapes, found = {}, []
    for line in hlo.splitlines():
        m = inst.search(line)
        if not m:
            continue
        name, shape, op, args = m.groups()
        shapes[name] = shape
        staged = op.startswith("copy") and any(
            "S(1)" in sh for sh in [shape] + [
                shapes.get(a.strip().lstrip("%"), "") for a in args.split(",")])
        if op in _POOL_MOVES and dim.search(shape) and not staged:
            found.append(line.strip())
    return found


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


def _paged_args(sharding, int8, lanes=LANES, pages_per_lane=36, geometry=None,
                n_layers=2):
    """Paged-attention operands: llama3.2-1b's heads by default, else
    ``geometry`` = (H_kv, G, D) over a pool of 4097 pages; the pools are
    ``n_layers`` stacked and lane-dense."""
    if geometry is None:
        hkv, d = LLAMA.n_kv_heads, LLAMA.resolved_head_dim
        g = LLAMA.n_heads // hkv
        n_pages = lanes * pages_per_lane + 1
    else:
        (hkv, g, d), n_pages = geometry, 4097
    ps = 16
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    dt = jnp.int8 if int8 else jnp.bfloat16
    pool = (n_layers, n_pages, ps, hkv * d)
    args = [s((lanes, hkv, g, d), jnp.bfloat16), s(pool, dt), s(pool, dt),
            s((lanes, pages_per_lane), jnp.int32), s((lanes,), jnp.int32),
            s((), jnp.int32)]
    if int8:
        args += [s((n_layers, n_pages, 2, hkv * ps), jnp.float32)]
    return args


def _paged(attn):
    def call(q, kp, vp, tables, lengths, layer, scales=None):
        return attn(q, kp, vp, tables, lengths, layer, scales=scales)
    return call


# the served geometry: 32 lanes of 257-page tables (a table that is not a
# multiple of the block), H_kv 8 with D 128, G 4 (mistral-nemo-12b) and
# D 64, G 3 (granite-3.0-3b-a800m), in stacks of 10 and 32 layers
SERVED = {"nemo-int8": ((8, 4, 128), 10), "granite-int8": ((8, 3, 64), 32)}


@pytest.mark.parametrize("int8, geometry, n_layers", [
    (False, None, 2), (True, None, 2), *[(True, *g) for g in SERVED.values()]],
    ids=["bf16", "int8", *SERVED])
def test_paged_attention_compiles(one_chip, int8, geometry, n_layers):
    """The kernel compiles on stacked, lane-dense pools and reads them as
    they are: no operand is copied (relaid out) on its way in."""
    kw = {} if geometry is None else dict(lanes=32, pages_per_lane=257,
                                          geometry=geometry)
    args = _paged_args(one_chip, int8, n_layers=n_layers, **kw)
    hlo = _compile(_paged(paged_attention), *args)
    assert "tpu_custom_call" in hlo
    assert not _pool_ops(hlo, args[1].shape[1])


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
    lanes = P(None, None, None, "model")     # the pools' head-major lane axis
    heads = {0: P(None, "model"), 1: lanes, 2: lanes, 6: lanes}
    for i, spec in heads.items():
        args[i] = jax.ShapeDtypeStruct(args[i].shape, args[i].dtype,
                                       sharding=NamedSharding(tp4, spec))
    with tp4:
        hlo = _compile(_paged(sharded_paged_attention), *args)
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo and "all-reduce" not in hlo


# decode steps the layer scan runs: int8 GQA pools with D 64 (granite's) and
# D 128 (nemo's), and bf16 latent pools with a leading unscanned block
# (DeepSeek-V2-Lite's latent widths); each two scanned periods, over the
# served cells' pool of 4097 pages (nothing is allocated to compile; a pool
# of a few hundred pages fits on chip, where the compiler stages it whole)
DECODE_STACKS = {
    "gqa-int8-d64": lambda: reduced(get_config("llama3.2-1b")).with_(
        n_heads=24, n_kv_heads=8, head_dim=64, kv_cache_dtype="int8"),
    "gqa-int8-d128": lambda: reduced(get_config("llama3.2-1b")).with_(
        n_heads=32, n_kv_heads=8, head_dim=128, kv_cache_dtype="int8"),
    "mla-bf16": lambda: reduced(get_config("deepseek-v2-lite")).with_(
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128)),
}


@pytest.mark.parametrize("stack", sorted(DECODE_STACKS))
def test_decode_step_keeps_the_page_pool_in_place(one_chip, on_tpu, stack):
    """The engine's decode step writes and reads the stacked page pools in
    place through the layer scan: the compiled program holds no copy, slice,
    write-back or transpose of a pool (one layer's or the stack), and its
    temporaries stay below one layer's pool."""
    cfg = DECODE_STACKS[stack]().with_(remat=False, paged_attn_impl="pallas")
    lanes, cache_len, ps, n_pages = 8, 512, 16, 4097
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    pool = on_chip(paged_cache_shapes(cfg, lanes, cache_len, ps, n_pages))
    assert len(pool["blocks"]) == 1 and pool["blocks"][0]
    n_periods = jax.tree_util.tree_leaves(pool["blocks"])[0].shape[0]
    assert n_periods >= 2 and bool(pool["head_blocks"]) == (stack == "mla-bf16")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lane = lambda dt: s((lanes,), dt)
    compiled = _jitted_decode_sample(cfg).lower(
        on_chip(param_shapes(cfg)), lane(jnp.int32), pool, lane(jnp.bool_),
        lane(jnp.float32), lane(jnp.int32), lane(jnp.bool_),
        s((lanes, 2), jnp.uint32), False).compile()
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == stack.startswith("gqa")
    assert not _pool_ops(hlo, n_pages)
    layer_pool = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(pool["blocks"])) // n_periods
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool
