"""Paged KV-cache subsystem: pool bookkeeping invariants, page scatter,
defrag, and the Pallas paged-attention kernel vs its jnp twin.

Engine-level paged-vs-slot output equivalence lives in test_serving.py;
this file covers the subsystem's own contracts: pages are allocated
lowest-first and exactly once, reservations make mid-decode exhaustion
impossible, freed pages return to the pool the same call, defrag moves
pool rows and tables consistently, and the trash page is never handed out.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import (
    page_bytes, paged_attention, paged_attention_ref, pages_per_block)
from repro.models import init_params, prefill
from repro.paging import PageManager, PagedCache


# ---------------------------------------------------------------------------
# PageManager (host bookkeeping)
# ---------------------------------------------------------------------------

def test_manager_alloc_free_reuse():
    mgr = PageManager(n_pages=8, page_size=4, n_lanes=3, max_pages_per_lane=4)
    assert mgr.free_pages == 7  # page 0 reserved (trash)
    mgr.admit(0, reserve_tokens=12)           # 3 pages promised
    assert mgr.available == 4
    got = mgr.alloc(0, 2)
    assert got == [1, 2]                      # lowest-first, deterministic
    assert mgr.block_tables[0, :2].tolist() == [1, 2]
    assert mgr.pages_in_use == 2 and mgr.outstanding == 1
    # growth within reservation
    assert mgr.ensure(0, tokens=9) == [3]     # 9 rows -> 3 pages
    assert mgr.ensure(0, tokens=9) == []      # idempotent
    # a second lane shares the pool
    mgr.admit(1, reserve_tokens=8)
    assert mgr.alloc(1, 2) == [4, 5]
    # free returns pages the same call; tables point at the trash page
    assert mgr.free_lane(0) == 3
    assert mgr.block_tables[0].tolist() == [0, 0, 0, 0]
    assert mgr.free_pages == 5 and mgr.lengths[0] == 0
    # freed ids are reused lowest-first
    mgr.admit(2, reserve_tokens=4)
    assert mgr.alloc(2, 1) == [1]


def test_manager_reservations_guard_exhaustion():
    mgr = PageManager(n_pages=6, page_size=4, n_lanes=4, max_pages_per_lane=4)
    mgr.admit(0, reserve_tokens=12)           # 3 of 5 pages promised
    assert mgr.can_admit(8) and not mgr.can_admit(12)
    with pytest.raises(RuntimeError, match="overcommit"):
        mgr.admit(1, reserve_tokens=16)
    mgr.admit(1, reserve_tokens=8)
    assert mgr.available == 0
    # materializing stays within the promises even at zero availability
    assert mgr.alloc(0, 3) and mgr.alloc(1, 2)
    with pytest.raises(RuntimeError, match="exhausted"):
        mgr.alloc(1, 1)
    with pytest.raises(ValueError, match="pages"):
        mgr.admit(2, reserve_tokens=100)      # wider than a block table
    with pytest.raises(RuntimeError, match="already holds"):
        mgr.admit(0, reserve_tokens=4)


def test_manager_defrag_compacts():
    mgr = PageManager(n_pages=10, page_size=4, n_lanes=3, max_pages_per_lane=3)
    for lane in range(3):
        mgr.admit(lane, reserve_tokens=12)
        mgr.alloc(lane, 3)
    mgr.free_lane(1)                          # pages {4,5,6} go free
    moves = mgr.defrag()
    # lane 2's pages {7,8,9} compact into the freed low ids
    assert sorted(m[0] for m in moves) == [7, 8, 9]
    assert sorted(m[1] for m in moves) == [4, 5, 6]
    used = {p for pages in mgr.lane_pages for p in pages}
    assert used == set(range(1, 7)) and mgr.defrag() == []
    # tables track the remap
    assert mgr.block_tables[2, :3].tolist() == mgr.lane_pages[2]


# ---------------------------------------------------------------------------
# PagedCache (device pools)
# ---------------------------------------------------------------------------

def _single_prefill(cfg, params, n_tokens, cache_len, seed=1):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(seed), (1, n_tokens),
                                          0, cfg.vocab_size)}
    _, single = prefill(params, cfg, batch, cache_len=cache_len)
    return single


def test_paged_cache_insert_roundtrip():
    cfg = reduced(get_config("llama3.2-1b")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool = PagedCache(cfg, n_lanes=3, cache_len=32, page_size=8)
    mgr = pool.manager

    single = _single_prefill(cfg, params, n_tokens=8, cache_len=16)
    mgr.admit(1, reserve_tokens=16)
    page_ids = mgr.alloc(1, 2)                # 16 rows = 2 pages
    mgr.set_length(1, 8)
    pool.insert(single, 1, page_ids, new_len=8)

    assert pool.pos.tolist() == [0, 8, 0]
    tables = np.asarray(pool.cache["block_tables"])
    assert tables[1, :2].tolist() == page_ids
    # the lane's pages hold exactly the contiguous prefill rows ...
    kp = np.asarray(pool.cache["blocks"][0]["kp"])      # (periods, n_pages, ps, H, D)
    k_one = np.asarray(single["blocks"][0]["k"][:, 0])  # (periods, 16, H, D)
    gathered = kp[:, page_ids].reshape(k_one.shape)
    np.testing.assert_array_equal(gathered, k_one)
    # ... and unallocated pages (incl. the trash page) stay zero
    untouched = [p for p in range(pool.n_pages) if p not in page_ids]
    assert not kp[:, untouched].any()


def test_paged_cache_defrag_preserves_lane_contents():
    cfg = reduced(get_config("llama3.2-1b")).with_(remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool = PagedCache(cfg, n_lanes=3, cache_len=32, page_size=8)
    mgr = pool.manager
    for lane, n in ((0, 16), (1, 16), (2, 16)):
        single = _single_prefill(cfg, params, n, cache_len=16, seed=lane + 1)
        mgr.admit(lane, reserve_tokens=16)
        ids = mgr.alloc(lane, 2)
        mgr.set_length(lane, n)
        pool.insert(single, lane, ids, new_len=n)

    def lane_rows(lane):
        kp = np.asarray(pool.cache["blocks"][0]["kp"])
        tbl = np.asarray(pool.cache["block_tables"])[lane, :2]
        return kp[:, tbl].copy()

    before = lane_rows(2)
    pool.free(1)
    assert len(pool.defrag()) > 0             # lane 2 compacted downward
    np.testing.assert_array_equal(lane_rows(2), before)
    assert {p for pages in mgr.lane_pages for p in pages} == set(range(1, 5))


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel vs jnp twin
# ---------------------------------------------------------------------------

# (page_size, table pages, lengths from block rows and table rows) per
# case; the block cases follow the kernel's own block size, with a table of
# two whole blocks and a ragged third
_PA_CASES = {
    "mixed": (8, 4, lambda rows, full: [1, 17, 32]),
    "block_edges": (16, None, lambda rows, full: [rows - 1, rows, rows + 1,
                                                 2 * rows - 1, 2 * rows, 2 * rows + 1]),
    "ragged_table": (16, None, lambda rows, full: [2 * rows + 3, full - 1, full]),
    "inactive_lanes": (16, None, lambda rows, full: [1, full, 1, rows + 5, 1]),
    "whole_table": (16, None, lambda rows, full: [full]),
}


def _pool_page_shapes(n_pages, ps, hkv, d, dtype, int8, n_layers=2):
    """Stacked K/V pools (and the int8 scale pool) in the kernel's layout."""
    kv = jax.ShapeDtypeStruct((n_layers, n_pages, ps, hkv * d), dtype)
    scale = jax.ShapeDtypeStruct((n_layers, n_pages, 2, hkv * ps), jnp.float32)
    return [kv, kv] + ([scale] if int8 else [])


def _pa_inputs(rng, case, int8, bf16=False, n_layers=2):
    b_ps, n_tbl, lens = _PA_CASES[case]
    hkv, g, d, ps = 2, 4, 32, b_ps
    pool_dt = jnp.int8 if int8 else (jnp.bfloat16 if bf16 else jnp.float32)
    nbytes = page_bytes(_pool_page_shapes(1, ps, hkv, d, pool_dt, int8))
    if n_tbl is None:
        ppb = pages_per_block(ps, nbytes, 1 << 20)
        n_tbl = 2 * ppb + ppb // 2 + 1
    ppb = pages_per_block(ps, nbytes, n_tbl)
    lengths = lens(ppb * ps, n_tbl * ps)
    b = len(lengths)
    n_pages = b * n_tbl + 1
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:b * n_tbl]
                         .reshape(b, n_tbl), jnp.int32)
    kv_shape = (n_layers, n_pages, ps, hkv * d)
    if int8:
        kp = jnp.asarray(rng.integers(-127, 128, kv_shape), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, kv_shape), jnp.int8)
        scales = dict(scales=jnp.asarray(
            rng.uniform(0.005, 0.02, (n_layers, n_pages, 2, hkv * ps)), jnp.float32))
    else:
        kp = jnp.asarray(rng.normal(size=kv_shape), pool_dt)
        vp = jnp.asarray(rng.normal(size=kv_shape), pool_dt)
        scales = {}
    return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32), scales


def _layer_of(pools, layer):
    """Layer ``layer`` of stacked pools, as a stack of one."""
    return {k: v[layer][None] for k, v in pools.items()}


@pytest.mark.parametrize("int8, case", [
    pytest.param(False, "mixed", id="False"),        # partial / multi / full
    pytest.param(True, "mixed", id="True"),
    *[pytest.param(int8, case, id=f"{'int8' if int8 else 'bf16'}-{case}")
      for case in ("block_edges", "ragged_table", "inactive_lanes", "whole_table")
      for int8 in (False, True)],
])
def test_paged_attention_kernel_matches_ref(int8, case):
    rng = np.random.default_rng(0)
    q, kp, vp, tables, lengths, scales = _pa_inputs(
        rng, case, int8, bf16=case != "mixed")
    # the last layer of the stack, against the reference on its pages alone
    one = _layer_of(dict(kp=kp, vp=vp, **scales), -1)
    ref = paged_attention_ref(q, one.pop("kp"), one.pop("vp"), tables, lengths,
                              0, **one)
    out = paged_attention(q, kp, vp, tables, lengths, kp.shape[0] - 1,
                          interpret=True, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_ignores_stale_pages():
    """Rows past ``lengths`` — stale data in partially-filled pages, trash
    rows, other lanes' leftovers, other layers' pages — must not leak into
    the output."""
    rng = np.random.default_rng(1)
    b, hkv, g, d, ps, n_pages = 1, 1, 2, 16, 4, 8
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(2, n_pages, ps, hkv * d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(2, n_pages, ps, hkv * d)), jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)

    def attend(kp, vp, length):
        return paged_attention(q, kp, vp, tables, jnp.asarray([length]), 0,
                               interpret=True)

    out5 = attend(kp, vp, 5)
    # poison everything past row 5
    kp2 = kp.at[0, 1, 1:].set(99.0).at[0, 2].set(-99.0)
    vp2 = vp.at[0, 1, 1:].set(99.0).at[0, 2].set(-99.0)
    out5b = attend(kp2, vp2, 5)
    # row 5 = page 1, offset 1 -> that row matters, rows 6+ don't
    kp3 = kp.at[0, 2, 2:].set(99.0)
    vp3 = vp.at[0, 2, 2:].set(99.0)
    out6 = attend(kp3, vp3, 6)
    ref6 = attend(kp, vp, 6)
    assert not np.allclose(np.asarray(out5), np.asarray(out5b))  # valid row changed
    np.testing.assert_allclose(np.asarray(out6), np.asarray(ref6))
    # the other layer's pages are never read
    np.testing.assert_array_equal(
        np.asarray(attend(kp.at[1].set(99.0), vp.at[1].set(-99.0), 6)),
        np.asarray(ref6))

    # table entries past a lane's last page (stale or trash ids) are fetched
    # with the lane's last block but never attended: poisoning the pages
    # they name leaves the output unchanged
    q, kp, vp, tables, _, _ = _pa_inputs(rng, "whole_table", False)
    ps = kp.shape[2]
    lengths = jnp.asarray([3 * ps + 5], jnp.int32)   # 4 live pages of a block
    out = paged_attention(q, kp, vp, tables, lengths, 0, interpret=True)
    dead = tables[0, 4:]
    kp2, vp2 = kp.at[:, dead].set(99.0), vp.at[:, dead].set(-99.0)
    out2 = paged_attention(q, kp2, vp2, tables, lengths, 0, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ---------------------------------------------------------------------------
# Decode over stacked, lane-dense pools vs the slot cache
# ---------------------------------------------------------------------------

# int8 and bf16 GQA pools; a stack with an unscanned tail block; MLA latent
# pools with an unscanned head block (DeepSeek-V2-Lite's leading dense layer)
_STACKS = {
    "gqa-bf16": lambda: reduced(get_config("llama3.2-1b")),
    "gqa-int8": lambda: reduced(get_config("llama3.2-1b")).with_(kv_cache_dtype="int8"),
    "gqa-tail": lambda: reduced(get_config("llama3.2-1b")).with_(
        block_pattern=("attn", "attn"), n_layers=5, kv_cache_dtype="int8"),
    "mla-head": lambda: reduced(get_config("deepseek-v2-lite")),
}
_PS, _TBL = 8, 4                       # pages of 8 rows, 4 pages a lane
_LENS = (5, 9, 17)                     # prompt rows per lane
# lanes serving at each decode step: lane 1 finishes after the first step
_ACTIVE = ([True, True, True], [True, False, True], [True, False, True])


def _stack_cfg(stack, impl="jnp"):
    return _STACKS[stack]().with_(remat=False, paged_attn_impl=impl)


def _admitted(cfg, params):
    """The prompts (padded to a lane's rows), and a slot cache and a paged
    pool holding them prefilled."""
    from repro.models.model import prefill as model_prefill
    from repro.paging import paged_insert_many

    cache_len = _PS * _TBL
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (len(_LENS), cache_len)),
                         jnp.int32)
    lengths = jnp.asarray(_LENS, jnp.int32)
    _, slot = model_prefill(params, cfg, {"tokens": tokens}, cache_len, lengths)
    pool = PagedCache(cfg, n_lanes=len(_LENS), cache_len=cache_len, page_size=_PS)
    ids = []
    for lane, n in enumerate(_LENS):
        pool.manager.admit(lane, reserve_tokens=cache_len)
        ids.append(pool.manager.alloc(lane, _TBL))
        pool.manager.set_length(lane, n)
    lanes = list(range(len(_LENS)))
    pool.cache = paged_insert_many(
        pool.cache, slot, jnp.asarray(lanes), jnp.asarray(ids, jnp.int32),
        jnp.asarray(pool.manager.block_tables), lengths, len(lanes))
    return tokens, slot, pool


def _pool_leaves(cache):
    """(path, leaf) of every page-pool leaf, scanned or not."""
    from repro.models.kvcache import POOL_KEYS

    return [(path, leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(cache)
            if path[-1].key in POOL_KEYS]


@pytest.mark.parametrize("stack", sorted(_STACKS))
def test_paged_decode_matches_the_slot_cache(stack):
    """Over decode steps with mixed active and idle lanes, the jnp twin on
    stacked lane-dense pools gives the slot cache's logits bit for bit on
    every active lane; an idle lane writes only the trash page, in every
    layer; a defrag move of the pool's pages leaves the next step's logits
    unchanged.  Unscanned blocks hold the scanned blocks' per-layer
    shapes."""
    from repro.models.model import decode_step
    from repro.paging.cache import _move_pages

    cfg = _stack_cfg(stack)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts, slot, pool = _admitted(cfg, params)
    tokens, paged = prompts[:, 0], pool.cache
    scanned = {p[-1].key: l.shape[1:] for p, l in _pool_leaves(paged["blocks"])}
    unscanned = _pool_leaves([paged["head_blocks"], paged["tail_blocks"]])
    assert bool(unscanned) == (stack in ("gqa-tail", "mla-head"))
    for path, leaf in unscanned:
        assert leaf.shape == scanned[path[-1].key]

    step = jax.jit(lambda c, t, a: decode_step(params, cfg, t, c, a))
    for active in _ACTIVE:
        active = jnp.asarray(active)
        before = jax.tree_util.tree_map(np.asarray, paged)
        want, slot = step(slot, tokens, active)
        got, paged = step(paged, tokens, active)
        act = np.asarray(active)
        np.testing.assert_array_equal(np.asarray(got)[act], np.asarray(want)[act])
        # the pages written: each active lane's page at its position, and
        # the trash page for the idle lanes
        tables, pos = np.asarray(before["block_tables"]), np.asarray(before["pos"])
        written = {int(tables[b, pos[b] // _PS]) for b in np.flatnonzero(act)} | {0}
        for (path, old), (_, new) in zip(_pool_leaves(before), _pool_leaves(paged)):
            page_axis = old.ndim - (3 if path[-1].key != "krp" else 2)
            diff = np.asarray(new) != old
            changed = set(np.flatnonzero(diff.any(axis=tuple(
                i for i in range(old.ndim) if i != page_axis))).tolist())
            assert changed <= written, (path, changed, written)
        tokens = jnp.argmax(want, axis=-1).astype(jnp.int32)

    # compaction: move every lane's pages up by one free page, tables with it
    tables = np.asarray(paged["block_tables"])
    live = sorted({int(p) for p in tables[:, :_TBL].ravel()})
    spare = pool.n_pages - 1
    moves = {p: spare - i for i, p in enumerate(reversed(live))}
    paged = _move_pages(paged, jnp.asarray(list(moves)), jnp.asarray(list(moves.values())))
    paged["block_tables"] = jnp.asarray(np.vectorize(moves.get)(tables), jnp.int32)
    active = jnp.asarray(_ACTIVE[-1])
    want, _ = step(slot, tokens, active)
    got, _ = step(paged, tokens, active)
    act = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(got)[act], np.asarray(want)[act])


@pytest.mark.parametrize("stack", ["gqa-bf16", "gqa-int8"])
def test_paged_decode_kernel_matches_the_jnp_twin(stack):
    """The Pallas kernel (interpreted) on the stacked pools picks the jnp
    twin's tokens at every step, idle lanes among them."""
    from repro.models.model import decode_step

    outs = {}
    for impl in ("jnp", "pallas_interpret"):
        cfg = _stack_cfg(stack, impl)
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompts, _, pool = _admitted(cfg, params)
        tokens, cache, picked = prompts[:, 0], pool.cache, []
        for active in _ACTIVE:
            active = jnp.asarray(active)
            logits, cache = decode_step(params, cfg, tokens, cache, active)
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            picked.append(np.asarray(tokens)[np.asarray(active)])
        outs[impl] = np.concatenate(picked)
    np.testing.assert_array_equal(outs["pallas_interpret"], outs["jnp"])


@pytest.mark.parametrize("stack", ["gqa-bf16", "gqa-int8", "gqa-tail"])
def test_paged_verify_and_chunk_steps_match_decode(stack):
    """The verify step over the stacked pools picks what W sequential
    decode steps pick, and a prompt admitted in chunks decodes as the
    one-shot admission does."""
    from repro.models.model import decode_step, verify_step
    from repro.paging import make_chunk_step

    cfg = _stack_cfg(stack)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts, _, pool = _admitted(cfg, params)
    tokens, w = prompts[:, 0], 3
    window = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (len(_LENS), w)), jnp.int32).at[:, 0].set(tokens)
    verify, _ = verify_step(params, cfg, window, pool.cache)
    cache, rows = pool.cache, []
    for c in range(w):
        logits, cache = decode_step(params, cfg, window[:, c], cache)
        rows.append(logits)
    np.testing.assert_array_equal(np.argmax(np.asarray(verify), -1),
                                  np.argmax(np.stack(rows, 1), -1))

    # lane 2's prompt again, in chunks of one page, into lane 1's pages
    chunk = jax.jit(make_chunk_step(cfg, _PS))
    prompt = np.asarray(prompts[2, :_LENS[2]])
    cache = pool.cache
    for start in range(0, _LENS[2], _PS):
        part = prompt[start:start + _PS]
        n = len(part)
        logits, cache = chunk(params, cache,
                              jnp.asarray(np.pad(part, (0, _PS - n)))[None],
                              jnp.int32(1), jnp.asarray([start], jnp.int32),
                              jnp.asarray([n], jnp.int32))
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
    step_tokens = tokens.at[1].set(first).at[2].set(first)
    logits, _ = decode_step(params, cfg, step_tokens, cache)
    got = np.argmax(np.asarray(logits), -1)
    assert got[1] == got[2]
