"""Tensor-parallel sharded serving (``repro/shard/``).

Correctness contract: at tp=1 the mesh adds size-1 axes only, so every
trace-time constraint is trivial and greedy outputs are BITWISE the
unsharded engine's, across every engine mode (slot / paged / prefix /
spec).  At tp>1 the row-parallel psums change float accumulation order,
so logits are allclose-not-bitwise — the tests assert greedy *token
parity* (deterministic per platform) plus page-pool invariants under
eviction/defrag and the decode step staying traced-once.

Multi-device cases force a host mesh; run with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (CI does).  With
fewer devices those tests skip, so the tier-1 suite stays green on a
plain single-device run.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.api import (
    LLM,
    KVConfig,
    MeshConfig,
    RuntimeConfig,
    SchedulerConfig,
    SpecConfig,
)
from repro.configs import get_config
from repro.models import init_params
from repro.models.model import paged_cache_shapes
from repro.runtime.sharding import param_specs, pool_specs
from repro.shard import (
    build_mesh,
    make_host_mesh,
    mesh_axis_size,
    tree_device_bytes,
    validate_mesh_config,
)

needs_devices = lambda n: pytest.mark.skipif(
    jax.device_count() < n,
    reason=f"needs {n} devices (XLA_FLAGS=--xla_force_host_platform_"
           f"device_count={n})")


# -- config plumbing (no devices needed) ------------------------------------

def test_mesh_config_roundtrip():
    rt = RuntimeConfig(mesh=MeshConfig(tp=2, dp=1, enable=True))
    back = RuntimeConfig.from_dict(rt.to_dict())
    assert back.mesh == rt.mesh
    assert back.mesh.enabled
    # default config round-trips to the disabled mesh
    rt0 = RuntimeConfig.from_dict(RuntimeConfig().to_dict())
    assert rt0.mesh == MeshConfig() and not rt0.mesh.enabled


def test_mesh_config_validation():
    with pytest.raises(ValueError):
        MeshConfig(tp=0)
    with pytest.raises(ValueError):
        MeshConfig(axes=("model", "model"))
    # enable semantics: explicit enable=True at tp=1 builds a real mesh,
    # the default activates iff an axis exceeds 1
    assert MeshConfig(enable=True).enabled
    assert MeshConfig(tp=2).enabled
    assert not MeshConfig().enabled
    validate_mesh_config(MeshConfig(tp=2, enable=True))


def test_build_mesh_off_and_on():
    assert build_mesh(None) is None
    assert build_mesh(MeshConfig()) is None
    m = build_mesh(MeshConfig(enable=True))
    assert m is not None and m.shape == {"data": 1, "model": 1}
    assert mesh_axis_size(m, "model") == 1
    assert mesh_axis_size(None, "model") == 1


def test_host_mesh_device_count_error():
    """Satellite: asking for more devices than exist raises the actionable
    error (XLA_FLAGS hint), not jax's bare reshape failure."""
    need = jax.device_count() * 64
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_host_mesh(1, need)


# -- engine parity ----------------------------------------------------------

def _runtime(mesh_cfg, *, mode="slot", prefix=False, spec=False, chunk=None,
             n_slots=2, cache_len=64):
    kv = KVConfig(mode=mode, cache_len=cache_len, page_size=16,
                  prefix_cache=prefix)
    return RuntimeConfig(
        kv=kv,
        scheduler=SchedulerConfig(n_slots=n_slots, prefill_chunk=chunk),
        spec=SpecConfig(enabled=spec, k=3, drafter="ngram"),
        mesh=mesh_cfg, max_new_tokens=8, reduced=True)


def _serve(runtime, prompts, gen=8, arch="llama3.2-1b"):
    llm = LLM(arch=arch, runtime=runtime)
    engine = llm.build_engine(max(len(p) for p in prompts), gen)
    metrics = engine.run([(0, p, gen) for p in prompts])
    outs = [r.output_tokens
            for r in sorted(metrics.finished, key=lambda r: r.req_id)]
    return llm, engine, outs


def _prompts(cfg_vocab=512, shared=0, seed=0):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, cfg_vocab, shared).tolist()
    return [pre + rng.integers(0, cfg_vocab, n).tolist()
            for n in (13, 5, 17)]


@pytest.mark.parametrize("mode_kw", [
    dict(mode="slot"),
    dict(mode="paged"),
    dict(mode="paged", prefix=True, chunk=16),
    dict(mode="paged", spec=True),
], ids=["slot", "paged", "paged+prefix", "paged+spec"])
def test_tp1_mesh_bitwise_unsharded(mode_kw):
    """Tentpole acceptance: a genuine 1x1 mesh (enable=True at tp=1) runs
    the whole sharded path — committed params, pool shardings, trace-time
    constraints — and greedy outputs are bitwise the unsharded engine's in
    every engine mode."""
    prompts = _prompts(shared=8 if mode_kw.get("prefix") else 0)
    _, _, base = _serve(_runtime(MeshConfig(), **mode_kw), prompts)
    _, _, meshed = _serve(_runtime(MeshConfig(enable=True), **mode_kw),
                          prompts)
    assert base == meshed, "tp=1 mesh changed greedy outputs"


@needs_devices(2)
@pytest.mark.parametrize("tp", [2, 4])
def test_tp_token_parity(tp):
    """tp>1 reorders the row-parallel reductions (allclose, not bitwise);
    greedy token streams must still match the unsharded engine on a
    forced host mesh (deterministic per platform, so not flaky)."""
    if jax.device_count() < tp or jax.device_count() % tp:
        pytest.skip(f"needs a multiple of {tp} devices")
    prompts = _prompts()
    _, _, base = _serve(_runtime(MeshConfig(), mode="paged"), prompts)
    _, _, shard = _serve(_runtime(MeshConfig(tp=tp), mode="paged"), prompts)
    assert base == shard, f"tp={tp} diverged from unsharded tokens"


@needs_devices(2)
def test_tp_decode_traced_once_and_single_dispatch():
    """Acceptance: under a tp=2 mesh the decode step still traces ONCE per
    engine lifetime (block tables are uploaded replicated, pools are
    committed, so admissions/evictions never retrace), i.e. decode remains
    one pjit dispatch per step."""
    prompts = _prompts()
    llm, engine, _ = _serve(_runtime(MeshConfig(tp=2), mode="paged"), prompts)
    fn = engine._decode_sample
    jitted = getattr(fn, "__wrapped__", fn)  # _with_mesh wraps the pjit fn
    n_traces = jitted._cache_size()
    assert n_traces >= 1
    engine.run([(0, p, 4) for p in _prompts(seed=1)])
    assert jitted._cache_size() == n_traces, "decode retraced under mesh"


@needs_devices(2)
def test_tp_pool_invariants_under_eviction_and_defrag():
    """The sharded page pool keeps the host-side PageManager's invariants
    through admission churn, eviction and defrag — the block tables stay
    host-authoritative with the device pools sharded under them."""
    rt = _runtime(MeshConfig(tp=2), mode="paged", chunk=16, n_slots=2)
    rt = dataclasses.replace(
        rt, scheduler=dataclasses.replace(rt.scheduler,
                                          defrag_threshold=0.1))
    prompts = _prompts() + _prompts(seed=3)  # > lanes: queueing + eviction
    llm = LLM(arch="llama3.2-1b", runtime=rt)
    engine = llm.build_engine(max(len(p) for p in prompts), 8)
    engine.run([(i, p, 8) for i, p in enumerate(prompts)])
    engine.store.manager.check_invariants()
    assert engine.metrics.defrag_count >= 0  # defrag path exercised or not,
    # invariants above are the real assertion
    # every pool leaf is committed to the mesh (not single-device)
    pools = engine.store.cache
    leaves = jax.tree_util.tree_leaves(pools)
    assert any(len(l.sharding.device_set) > 1 for l in leaves
               if hasattr(l, "sharding")), "no pool leaf spans the mesh"


# -- big-model footprint (analytic + reduced dryrun) ------------------------

@needs_devices(4)
def test_mistral_large_tp4_footprint_analytic():
    """Acceptance: at mistral-large-123b scale, tp=4 holds per-device
    params + paged KV below half the unsharded footprint — computed
    analytically over eval_shape trees (no 123B allocation)."""
    cfg = get_config("mistral-large-123b").with_(remat=False)
    mesh = make_host_mesh(1, 4)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pshapes = paged_cache_shapes(cfg, 8, 4096, 16, 2048)

    def total_bytes(tree):
        return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                   for l in jax.tree_util.tree_leaves(tree))

    per_dev = (
        tree_device_bytes(shapes, param_specs(shapes, mesh, cfg, fsdp=False),
                          mesh)
        + tree_device_bytes(pshapes, pool_specs(pshapes, mesh, cfg.n_kv_heads),
                            mesh))
    unsharded = total_bytes(shapes) + total_bytes(pshapes)
    assert per_dev < unsharded / 2, (
        f"tp=4 per-device {per_dev/2**30:.1f} GiB not < "
        f"{unsharded/2**31:.1f} GiB (half of unsharded)")
    # the dominant leaves really split 4-ways
    assert per_dev < unsharded / 3


@needs_devices(4)
def test_mistral_large_reduced_tp4_decodes():
    """The same arch at reduced size actually initializes, shards and
    decodes on the tp=4 host mesh end to end (paged engine)."""
    rt = _runtime(MeshConfig(tp=4), mode="paged", cache_len=64)
    llm, _, outs = _serve(rt, _prompts(), gen=4, arch="mistral-large-123b")
    assert [len(o) for o in outs] == [4, 4, 4]
    # params were committed across the mesh
    leaves = jax.tree_util.tree_leaves(llm.params)
    assert any(len(l.sharding.device_set) == 4 for l in leaves)
