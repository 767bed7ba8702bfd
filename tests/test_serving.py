"""Serving consistency: prefill + stepwise decode == full-context forward,
and the continuous-batching engine == solo decoding of each request.

The strongest functional check of the KV-cache / recurrent-state machinery:
for every cache-bearing architecture family, decoding token t against the
cache must produce the same logits as a full forward pass over [0..t].
The engine tests extend that to slot scattering, padded prefill buckets,
staggered admission and slot reuse: scheduling must be output-invisible.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import decode_step, forward, init_params, prefill
from repro.serving import (
    EngineConfig,
    FIFOScheduler,
    Request,
    SamplingParams,
    ServingEngine,
    SlotCache,
    sample_tokens,
)

# One representative per cache mechanism:
#   GQA dense, MLA latents, MoE, MLA latents inside MoE blocks with YaRN,
#   mLSTM/sLSTM state, RG-LRU + local ring, enc-dec cross-attention.
ARCHS = [
    "llama3.2-1b",
    "minicpm3-4b",
    "granite-moe-3b-a800m",
    "deepseek-v2-lite",
    "xlstm-125m",
    "recurrentgemma-9b",
    "seamless-m4t-large-v2",
]

S_PROMPT, S_GEN, BATCH = 12, 4, 2


def _inputs(cfg, key, s):
    kt, ke = jax.random.split(key)
    if cfg.is_encoder_decoder:
        return {
            "src_embeds": jax.random.normal(ke, (BATCH, 8, cfg.d_model), jnp.float32) * 0.02,
            "tgt_tokens": jax.random.randint(kt, (BATCH, s), 0, cfg.vocab_size),
        }
    return {"tokens": jax.random.randint(kt, (BATCH, s), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = reduced(get_config(arch)).with_(remat=False)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, jax.random.fold_in(key, 1))

    total = S_PROMPT + S_GEN
    full_batch = _inputs(cfg, jax.random.fold_in(key, 2), total)
    tok_key = "tgt_tokens" if cfg.is_encoder_decoder else "tokens"
    all_tokens = full_batch[tok_key]

    # reference: full-context forward logits at each position
    ref_logits = forward(params, cfg, full_batch)

    # prefill on the prompt, then decode the remaining tokens one by one
    pre_batch = dict(full_batch)
    pre_batch[tok_key] = all_tokens[:, :S_PROMPT]
    logits, cache = prefill(params, cfg, pre_batch, cache_len=total)
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(ref_logits[:, S_PROMPT - 1, :], np.float32),
        rtol=2e-2, atol=2e-2,
    )

    for t in range(S_PROMPT, total):
        logits, cache = decode_step(params, cfg, all_tokens[:, t], cache)
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(ref_logits[:, t, :], np.float32),
            rtol=2e-2, atol=2e-2,
            err_msg=f"{arch}: decode position {t}",
        )


def test_serve_batch_driver_runs():
    from repro.launch.serve import serve_batch

    cfg = reduced(get_config("llama3.2-1b")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)}
    out, timings = serve_batch(cfg, params, batch, cache_len=16, gen_tokens=5)
    assert out.shape == (2, 5)
    assert timings["prefill_s"] > 0 and timings["decode_s"] > 0
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < cfg.vocab_size).all()


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------

# one attention-family arch exercising padded prefill buckets (MLA has the
# most intricate cache) + one recurrent arch on the exact-length path; both
# produce varied greedy continuations at smoke scale (llama's random init
# collapses to a repeated token, which would mask pos-bookkeeping bugs).
ENGINE_CASES = [
    ("llama3.2-1b", (8, 16)),
    ("minicpm3-4b", (8, 16)),
    ("xlstm-125m", None),
]


def _engine_setup(arch, buckets, n_slots=2, cache_len=32, ecfg_kw=None, **cfg_kw):
    cfg = reduced(get_config(arch)).with_(remat=False, **cfg_kw)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(n_slots=n_slots, cache_len=cache_len,
                        prefill_buckets=buckets, **(ecfg_kw or {}))
    return cfg, params, ServingEngine(cfg, params, ecfg)


@pytest.mark.parametrize("arch,buckets", ENGINE_CASES)
def test_engine_matches_solo_staggered(arch, buckets):
    """Acceptance: unequal-length requests arriving staggered, with more
    requests than slots (queueing + eviction + slot reuse), each produce
    EXACTLY the greedy tokens of a solo serve_batch run of that request."""
    from repro.launch.serve import serve_batch

    cfg, params, engine = _engine_setup(arch, buckets)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (5, 9, 3, 7)]
    gens = [6, 4, 8, 5]
    arrivals = [(0, prompts[0], gens[0]), (0, prompts[1], gens[1]),
                (2, prompts[2], gens[2]), (4, prompts[3], gens[3])]
    metrics = engine.run(arrivals)

    assert len(metrics.finished) == 4
    by_id = {r.req_id: r for r in metrics.finished}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        solo, _ = serve_batch(cfg, params,
                              {"tokens": jnp.asarray([p], jnp.int32)},
                              cache_len=engine.engine_cfg.cache_len,
                              gen_tokens=g)
        assert by_id[i].output_tokens == np.asarray(solo)[0].tolist(), (
            f"{arch}: request {i} diverged from its solo decode")
    rep = metrics.report()
    assert rep["generated_tokens"] == sum(gens)
    assert rep["prefills"] == 4
    assert rep["ttft_mean_s"] > 0 and rep["latency_mean_s"] > 0


def test_engine_int8_kv_parity():
    """Satellite: greedy decode through the engine with the byte-size int8
    KV cache tracks the bf16 cache within quantization tolerance.  Token
    streams feed back into the model, so one early flip cascades — require
    exact first tokens (pure prefill logits) and high overall agreement."""
    outs = {}
    for kv in ("bf16", "int8"):
        cfg, params, engine = _engine_setup("minicpm3-4b", None,
                                            kv_cache_dtype=kv)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (6, 9, 4)]
        metrics = engine.run([(0, prompts[0], 5), (1, prompts[1], 5),
                              (2, prompts[2], 5)])
        outs[kv] = {r.req_id: r.output_tokens for r in metrics.finished}
    agree = 0
    total = 0
    for rid, ref in outs["bf16"].items():
        assert outs["int8"][rid][0] == ref[0], "first token must match"
        agree += sum(a == b for a, b in zip(outs["int8"][rid], ref))
        total += len(ref)
    assert agree / total >= 0.8, f"int8 KV agreement {agree}/{total}"


# ---------------------------------------------------------------------------
# Paged cache mode (repro/paging/)
# ---------------------------------------------------------------------------

# every cache mechanism the paged engine serves: GQA pools, MLA latent
# pools, MoE (attn pools + routed FFN), recurrent per-lane state (the
# degenerate paged case: no pools, block tables unused), and the hybrid
# rglru + local-attn ring (rings stay per-lane inside the paged tree)
PAGED_ARCHS = ["llama3.2-1b", "minicpm3-4b", "granite-moe-3b-a800m",
               "deepseek-v2-lite", "xlstm-125m", "recurrentgemma-9b"]
RECURRENT_ARCHS = {"xlstm-125m", "recurrentgemma-9b"}


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_engine_paged_matches_solo(arch):
    """Acceptance: the paged engine is output-invisible — staggered
    mixed-length requests (several spanning multiple pages), more requests
    than lanes, exact greedy match vs solo serve_batch.  page_size=8 with
    cache_len=32 makes the gathered view the slot shape, so the match is
    bitwise, not approximate."""
    from repro.launch.serve import serve_batch

    buckets = (8, 16) if arch not in RECURRENT_ARCHS else None
    cfg, params, engine = _engine_setup(
        arch, buckets, ecfg_kw=dict(cache_mode="paged", page_size=8))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (5, 13, 3, 17)]
    gens = [6, 4, 8, 5]
    arrivals = [(0, prompts[0], gens[0]), (0, prompts[1], gens[1]),
                (2, prompts[2], gens[2]), (4, prompts[3], gens[3])]
    metrics = engine.run(arrivals)

    assert len(metrics.finished) == 4
    by_id = {r.req_id: r for r in metrics.finished}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        solo, _ = serve_batch(cfg, params,
                              {"tokens": jnp.asarray([p], jnp.int32)},
                              cache_len=engine.engine_cfg.cache_len,
                              gen_tokens=g)
        assert by_id[i].output_tokens == np.asarray(solo)[0].tolist(), (
            f"{arch}: paged request {i} diverged from its solo decode")
    # eviction returned every page to the pool the same run
    assert engine.store.manager.pages_in_use == 0 if engine._has_paged_kinds else True


@pytest.mark.parametrize("arch", ["llama3.2-1b", "minicpm3-4b"])
def test_engine_chunked_prefill_matches_solo(arch):
    """Chunked admission (prompts spanning several page-sized chunks,
    interleaved with running decodes) produces exactly the solo stream."""
    from repro.launch.serve import serve_batch

    cfg, params, engine = _engine_setup(
        arch, None, ecfg_kw=dict(cache_mode="paged", page_size=8,
                                 prefill_chunk=8))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (13, 21, 3, 17)]
    gens = [5, 4, 6, 5]
    arrivals = [(0, prompts[0], gens[0]), (0, prompts[1], gens[1]),
                (2, prompts[2], gens[2]), (4, prompts[3], gens[3])]
    metrics = engine.run(arrivals)

    assert metrics.chunk_steps >= 6  # 13 -> 2 chunks, 21 -> 3, 17 -> 3
    by_id = {r.req_id: r for r in metrics.finished}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        solo, _ = serve_batch(cfg, params,
                              {"tokens": jnp.asarray([p], jnp.int32)},
                              cache_len=engine.engine_cfg.cache_len,
                              gen_tokens=g)
        assert by_id[i].output_tokens == np.asarray(solo)[0].tolist(), (
            f"{arch}: chunked request {i} diverged from its solo decode")


def test_engine_paged_admissions_serialize_on_capacity():
    """Two requests that each fit but cannot fit TOGETHER must admit one
    after the other (reservation taken before the next capacity gate), not
    crash mid-step on an overcommitted pool."""
    cfg, params, engine = _engine_setup(
        "llama3.2-1b", None,
        ecfg_kw=dict(cache_mode="paged", page_size=8, n_pages=6,
                     max_prefills_per_step=2))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 16).tolist() for _ in range(2)]
    metrics = engine.run([(0, prompts[0], 8), (0, prompts[1], 8)])
    assert len(metrics.finished) == 2
    assert metrics.peak_running == 1      # 3+3 pages never fit 5 at once
    assert engine.store.manager.pages_in_use == 0


def test_engine_paged_int8_matches_slot_int8():
    """int8 byte-size pages quantize exactly like the int8 slot cache, so
    the two modes' greedy streams are identical (not merely close)."""
    outs = {}
    for mode in ("slot", "paged"):
        cfg, params, engine = _engine_setup(
            "llama3.2-1b", None, kv_cache_dtype="int8",
            ecfg_kw=dict(cache_mode=mode, page_size=8))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (6, 11, 4)]
        metrics = engine.run([(0, prompts[0], 5), (1, prompts[1], 5),
                              (2, prompts[2], 5)])
        outs[mode] = {r.req_id: r.output_tokens for r in metrics.finished}
    assert outs["paged"] == outs["slot"]


def test_engine_counts_attn_blocks():
    """The paged-attention block counters follow the lanes' lengths: a lane
    attending rows 2r-5 .. 2r+3 (r rows to a kernel block) walks two blocks,
    then three from row 2r+1, and the idle lane one; every step counts
    2 lanes x the table's blocks (64 pages of 8 rows).  A stack with no K/V
    pool (MLA latents) counts nothing."""
    cfg, params, engine = _engine_setup(
        "llama3.2-1b", None, cache_len=512,
        ecfg_kw=dict(cache_mode="paged", page_size=8))
    rows, n_blocks = engine._attn_blocks
    assert rows % 8 == 0 and n_blocks == -(-64 // (rows // 8)) >= 3
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                               2 * rows - 6).tolist()
    rep = engine.run([(0, prompt, 10)]).report()
    assert rep["decode_steps"] == 9
    assert rep["attn_blocks_live"] == 6 * 2 + 3 * 3 + 9 * 1
    assert rep["attn_blocks_walked"] == 9 * 2 * n_blocks
    assert rep["attn_live_share"] == round(30 / (18 * n_blocks), 4)

    cfg, params, engine = _engine_setup(
        "minicpm3-4b", None, ecfg_kw=dict(cache_mode="paged", page_size=8))
    assert engine._attn_blocks is None
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 5).tolist()
    rep = engine.run([(0, prompt, 4)]).report()
    assert rep["attn_blocks_walked"] == 0 and rep["attn_live_share"] == 0.0


def test_engine_paged_decode_traced_once():
    """Acceptance: growth, admission, eviction and table refreshes never
    retrace the decode step (fixed shapes end to end)."""
    cfg, params, engine = _engine_setup(
        "llama3.2-1b", None, ecfg_kw=dict(cache_mode="paged", page_size=8,
                                          prefill_chunk=8))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (13, 5, 17, 4)]
    engine.run([(0, prompts[0], 6), (1, prompts[1], 4)])
    n_traces = engine._decode_sample._cache_size()
    assert n_traces >= 1
    engine.run([(0, prompts[2], 8), (0, prompts[3], 3)])
    assert engine._decode_sample._cache_size() == n_traces, (
        "decode step retraced mid-serve")


def test_free_lane_pos_stays_pinned():
    """Satellite: freed lanes' pos is reset inside the jitted step and no
    longer drifts upward on garbage decode tokens."""
    cfg, params, engine = _engine_setup("llama3.2-1b", None, n_slots=2)
    rng = np.random.default_rng(0)
    short = rng.integers(0, cfg.vocab_size, 4).tolist()
    long = rng.integers(0, cfg.vocab_size, 4).tolist()
    engine.run([(0, short, 2), (0, long, 12)])
    # lane 0 (short request) evicted many steps before lane 1 finished;
    # without the active mask its pos would have kept advancing
    assert engine.store.pos.tolist()[0] == 0


def test_engine_streaming_hooks():
    """Satellite: on_token callback fires for every token (in order), and
    the generator API yields the same stream the request records."""
    cfg, params, engine = _engine_setup("minicpm3-4b", None, n_slots=2)
    rng = np.random.default_rng(3)
    seen = []
    req = engine.add_request(rng.integers(0, cfg.vocab_size, 6).tolist(), 5,
                             on_token=seen.append)
    while engine.has_work:
        engine.step()
    assert seen == req.output_tokens and len(seen) == 5

    cfg, params, engine = _engine_setup("minicpm3-4b", None, n_slots=2)
    toks = list(engine.stream(rng.integers(0, cfg.vocab_size, 6).tolist(), 4))
    assert len(toks) == 4
    assert toks == engine.metrics.finished[0].output_tokens


def test_engine_rejects_bad_paged_configs():
    cfg = reduced(get_config("llama3.2-1b")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="cache_mode"):
        ServingEngine(cfg, params, EngineConfig(cache_mode="virtual"))
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(cfg, params, EngineConfig(cache_mode="slot", prefill_chunk=8))
    with pytest.raises(ValueError, match="multiple of"):
        ServingEngine(cfg, params, EngineConfig(cache_mode="paged", page_size=8,
                                                prefill_chunk=12))
    # a request whose worst-case reservation can never fit the pool must
    # fail fast, not stall the admission gate forever
    tiny = ServingEngine(cfg, params, EngineConfig(
        n_slots=2, cache_len=32, cache_mode="paged", page_size=8, n_pages=3))
    with pytest.raises(ValueError, match="pages"):
        tiny.add_request(list(range(1, 17)), max_new_tokens=8)  # needs 3 pages, has 2
    # MoE capacity depends on how many tokens share a dispatch -> unchunkable
    moe_cfg = reduced(get_config("granite-moe-3b-a800m")).with_(remat=False)
    moe_params = init_params(moe_cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="chunk"):
        ServingEngine(moe_cfg, moe_params,
                      EngineConfig(cache_mode="paged", page_size=8,
                                   prefill_chunk=8))


def test_engine_rejects_bad_configs():
    cfg = reduced(get_config("xlstm-125m")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="recurrent|state integrates"):
        ServingEngine(cfg, params,
                      EngineConfig(n_slots=2, cache_len=32, prefill_buckets=(8,)))
    _, _, engine = _engine_setup("llama3.2-1b", None, cache_len=16)
    with pytest.raises(ValueError, match="cache_len"):
        engine.add_request(list(range(1, 14)), max_new_tokens=8)


# ---------------------------------------------------------------------------
# Engine components (host-side units)
# ---------------------------------------------------------------------------

def test_fifo_scheduler_slots_and_queueing():
    sched = FIFOScheduler(n_slots=2, max_prefills_per_step=1)
    reqs = [Request(req_id=i, prompt=[1], max_new_tokens=1) for i in range(4)]
    for r in reqs:
        sched.submit(r)
    # one admission per step (interleave policy), lowest slot first
    assert [(r.req_id, s) for r, s in sched.schedule()] == [(0, 0)]
    assert [(r.req_id, s) for r, s in sched.schedule()] == [(1, 1)]
    assert sched.schedule() == []  # pool full, 2 still waiting
    assert sched.free_slots == 0 and len(sched.waiting) == 2
    done = sched.release(0)
    assert done.req_id == 0 and done.slot is None
    # freed slot is immediately reusable, FIFO order preserved
    assert [(r.req_id, s) for r, s in sched.schedule()] == [(2, 0)]
    sched.release(1)
    assert [(r.req_id, s) for r, s in sched.schedule()] == [(3, 1)]
    sched.release(0), sched.release(1)
    assert not sched.has_work and sched.free_slots == 2


def test_sample_tokens_policies():
    logits = jnp.asarray([[0.0, 1.0, 3.0, 2.0]] * 3, jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3))
    # greedy lanes: argmax regardless of key/temperature
    toks = sample_tokens(logits, jnp.ones((3,)), jnp.zeros((3,), jnp.int32),
                         jnp.ones((3,), bool), keys)
    assert toks.tolist() == [2, 2, 2]
    # top_k=1 equals greedy even when stochastic
    toks = sample_tokens(logits, jnp.full((3,), 5.0),
                         jnp.ones((3,), jnp.int32), jnp.zeros((3,), bool), keys)
    assert toks.tolist() == [2, 2, 2]
    # top_k=2 at high temperature only ever emits the top-2 set {2, 3}
    seen = set()
    for s in range(20):
        ks = jax.vmap(jax.random.PRNGKey)(jnp.arange(3) + 100 * s)
        toks = sample_tokens(logits, jnp.full((3,), 10.0),
                             jnp.full((3,), 2, jnp.int32),
                             jnp.zeros((3,), bool), ks)
        seen |= set(toks.tolist())
    assert seen == {2, 3}


def test_slot_cache_insert_free_roundtrip():
    cfg = reduced(get_config("llama3.2-1b")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool = SlotCache(cfg, n_slots=3, cache_len=16)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                                          cfg.vocab_size)}
    _, single = prefill(params, cfg, batch, cache_len=16)
    pool.insert(single, 1)
    assert pool.pos.tolist() == [0, 6, 0]
    # the lane's stacked-block K rows equal the batch=1 prefill cache ...
    k_pool = np.asarray(pool.cache["blocks"][0]["k"][:, 1])
    k_one = np.asarray(single["blocks"][0]["k"][:, 0])
    np.testing.assert_array_equal(k_pool, k_one)
    # ... and the other lanes stay zero
    assert not np.asarray(pool.cache["blocks"][0]["k"][:, 0]).any()
    pool.free(1)
    assert pool.pos.tolist() == [0, 0, 0]


def test_int8_kv_cache_close_to_bf16():
    """SPOGA-style byte-size KV cache: decode logits match the bf16-cache
    path within quantization error (beyond-paper feature)."""
    cfg = reduced(get_config("llama3.2-1b")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                          cfg.vocab_size)}
    outs = {}
    for kv in ("bf16", "int8"):
        c = cfg.with_(kv_cache_dtype=kv)
        logits, cache = prefill(params, c, batch, cache_len=16)
        for t in range(3):
            logits, cache = decode_step(
                params, c, jnp.full((2,), 7, jnp.int32), cache)
        outs[kv] = np.asarray(logits, np.float32)
    ref = outs["bf16"]
    scale = np.abs(ref).max()
    np.testing.assert_allclose(outs["int8"], ref, atol=0.08 * scale)
    # argmax (greedy token) should agree for nearly all positions
    assert (outs["int8"].argmax(-1) == ref.argmax(-1)).mean() >= 0.95


# -- chunked prefill for recurrent stacks (repro/paging/prefill.py) ---------

def _zero_cell_state(kind, cfg, p, b=1):
    d, hh = cfg.d_model, cfg.n_heads
    dh = d // hh
    f32 = jnp.float32
    if kind == "rglru":
        lru = p["conv_w"].shape[-1]
        return {"h": jnp.zeros((b, lru), f32),
                "conv": jnp.zeros((b, cfg.conv_width - 1, lru), f32)}
    if kind == "mlstm":
        return {"C": jnp.zeros((b, hh, dh, dh), f32),
                "n": jnp.zeros((b, hh, dh), f32)}
    return {"c": jnp.zeros((b, hh, dh), f32),
            "n": jnp.zeros((b, hh, dh), f32),
            "h": jnp.zeros((b, hh, dh), f32)}


@pytest.mark.parametrize("kind,arch", [
    ("rglru", "recurrentgemma-9b"),
    ("mlstm", "xlstm-125m"),
    ("slstm", "xlstm-125m"),
])
def test_recurrent_chunk_cells_match_block(kind, arch):
    """Unit contract for the state-carrying chunk cells: running a sequence
    through ``*_chunk`` in pieces (with a ragged, padded final chunk)
    matches the one-shot ``*_block`` on the valid prefix.  sLSTM is
    bitwise (identical sequential op order under the carry freeze);
    RG-LRU / mLSTM regroup their scans at chunk boundaries -> allclose."""
    from repro.models import recurrent as rec

    cfg = reduced(get_config(arch)).with_(remat=False)
    init = {"rglru": rec.init_rglru, "mlstm": rec.init_mlstm,
            "slstm": rec.init_slstm}[kind]
    block = {"rglru": rec.rglru_block, "mlstm": rec.mlstm_block,
             "slstm": rec.slstm_block}[kind]
    chunk = {"rglru": rec.rglru_chunk, "mlstm": rec.mlstm_chunk,
             "slstm": rec.slstm_chunk}[kind]
    p = init(jax.random.PRNGKey(0), cfg)
    s_valid, c_len = 21, 8  # 3 chunks, last one ragged (5 valid + 3 pad)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, s_valid, cfg.d_model),
                          jnp.bfloat16)
    ref, _ = block(x, p, cfg, None)

    state = _zero_cell_state(kind, cfg, p)
    outs = []
    for start in range(0, s_valid, c_len):
        n_valid = min(c_len, s_valid - start)
        xc = jnp.zeros((1, c_len, cfg.d_model), x.dtype)
        xc = xc.at[:, :n_valid].set(x[:, start:start + n_valid])
        o, state = chunk(xc, p, cfg, state, jnp.int32(n_valid))
        outs.append(np.asarray(o[:, :n_valid], np.float32))
    got = np.concatenate(outs, axis=1)
    ref = np.asarray(ref, np.float32)
    scale = max(np.abs(ref).max(), 1e-6)
    np.testing.assert_allclose(got, ref, atol=2e-2 * scale, rtol=0)


def test_engine_chunked_xlstm_matches_unchunked():
    """Satellite acceptance: an xLSTM stack admits long prompts in chunks
    (state carried across chunk boundaries, ragged lengths, lane reuse
    zeroing a freed lane's stale cell state) and produces the same greedy
    tokens as one-shot exact-length admission."""
    cfg = reduced(get_config("xlstm-125m")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # lengths straddle chunk multiples; > n_slots requests force lane reuse
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (13, 21, 8, 17)]
    outs = {}
    for chunk in (None, 8):
        engine = ServingEngine(params=params, cfg=cfg, engine_cfg=EngineConfig(
            n_slots=2, cache_len=48, cache_mode="paged", page_size=8,
            prefill_chunk=chunk))
        metrics = engine.run([(i, p, 6) for i, p in enumerate(prompts)])
        outs[chunk] = [r.output_tokens
                       for r in sorted(metrics.finished,
                                       key=lambda r: r.req_id)]
        if chunk:
            assert metrics.chunk_steps >= 6  # 13->2, 21->3, 8->1, 17->3
    assert outs[None] == outs[8], "chunked xLSTM diverged from one-shot"


def test_chunked_prefill_gate_tiers():
    """``chunkable_with_state`` admits pure-recurrent stacks to chunked
    prefill while the bitwise ``chunkable`` contract still excludes them
    (prefix cache / spec); local_attn ring buffers stay unchunkable."""
    from repro.paging import chunkable, chunkable_with_state

    xl = reduced(get_config("xlstm-125m")).with_(remat=False)
    assert not chunkable(xl) and chunkable_with_state(xl)
    rg = reduced(get_config("recurrentgemma-9b")).with_(remat=False)
    assert not chunkable_with_state(rg)  # local_attn in the pattern
    with pytest.raises(ValueError, match="chunk"):
        ServingEngine(rg, init_params(rg, jax.random.PRNGKey(0)),
                      EngineConfig(cache_mode="paged", page_size=8,
                                   prefill_chunk=8))


# -- DeadlineAdmission (ingress shedding) -----------------------------------

def test_deadline_admission_sheds_late():
    """Requests already past their deadline in the queue are shed at
    ingress: reason="deadline", a deadline_shed count, and the ordinary
    finish accounting (miss + zero goodput) — without ever holding a lane."""
    from repro.serving.policies import DeadlineAdmission, EnginePolicies

    cfg = reduced(get_config("llama3.2-1b")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params, EngineConfig(n_slots=1, cache_len=32),
        policies=EnginePolicies(admission=DeadlineAdmission()))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 8).tolist()
    # 1 lane, 3 requests, an impossible deadline: everything queued goes
    # stale immediately and must be shed rather than decoded late
    sp = SamplingParams(deadline_s=1e-6)
    metrics = engine.run([(0, prompt, 6, sp) for _ in range(3)])
    rep = metrics.report()
    assert rep["deadline_shed"] >= 2
    assert rep["requests"] == 3
    shed = [r for r in metrics.finished if r.finish_reason == "deadline"]
    assert len(shed) >= 2 and all(not r.output_tokens for r in shed)
    # shed requests are misses with zero goodput contribution
    assert rep["deadline_misses"] >= len(shed)
    # a generous deadline sheds nothing and finishes normally
    engine2 = ServingEngine(
        cfg, params, EngineConfig(n_slots=1, cache_len=32),
        policies=EnginePolicies(admission=DeadlineAdmission()))
    m2 = engine2.run([(0, prompt, 6, SamplingParams(deadline_s=300.0))
                      for _ in range(2)])
    assert m2.report()["deadline_shed"] == 0
    assert all(len(r.output_tokens) == 6 for r in m2.finished)


def test_deadline_admission_slack_and_validation():
    from repro.serving.policies import DeadlineAdmission

    with pytest.raises(ValueError):
        DeadlineAdmission(slack_s=-1.0)
    pol = DeadlineAdmission(slack_s=0.5)
    now = 100.0
    mk = lambda submit, dl: Request(req_id=0, prompt=[1], max_new_tokens=1,
                                    submit_time=submit, deadline_s=dl)
    # 0.4s left > would finish inside slack? shed when remaining < slack
    assert pol.shed([mk(99.0, 1.2)], now) == [0]   # 0.2s left < 0.5 slack
    assert pol.shed([mk(99.0, 2.0)], now) == []    # 1.0s left
    assert pol.shed([mk(99.0, None)], now) == []   # no deadline: never shed
