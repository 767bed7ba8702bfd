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
    paged_attention, paged_attention_ref, pages_per_block)
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


def _pa_inputs(rng, case, int8, bf16=False):
    b_ps, n_tbl, lens = _PA_CASES[case]
    hkv, g, d, ps = 2, 4, 32, b_ps
    pool_dt = jnp.int8 if int8 else (jnp.bfloat16 if bf16 else jnp.float32)
    if n_tbl is None:
        ppb = pages_per_block(ps, hkv, d, pool_dt, 1 << 20)
        n_tbl = 2 * ppb + ppb // 2 + 1
    ppb = pages_per_block(ps, hkv, d, pool_dt, n_tbl)
    lengths = lens(ppb * ps, n_tbl * ps)
    b = len(lengths)
    n_pages = b * n_tbl + 1
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:b * n_tbl]
                         .reshape(b, n_tbl), jnp.int32)
    if int8:
        kp = jnp.asarray(rng.integers(-127, 128, (n_pages, ps, hkv, d)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (n_pages, ps, hkv, d)), jnp.int8)
        scales = dict(
            k_scale=jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, ps, hkv)), jnp.float32),
            v_scale=jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, ps, hkv)), jnp.float32),
        )
    else:
        kp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), pool_dt)
        vp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), pool_dt)
        scales = {}
    return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32), scales


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
    ref = paged_attention_ref(q, kp, vp, tables, lengths, **scales)
    out = paged_attention(q, kp, vp, tables, lengths, interpret=True, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_ignores_stale_pages():
    """Rows past ``lengths`` — stale data in partially-filled pages, trash
    rows, other lanes' leftovers — must not leak into the output."""
    rng = np.random.default_rng(1)
    b, hkv, g, d, ps, n_pages = 1, 1, 2, 16, 4, 8
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    out5 = paged_attention(q, kp, vp, tables, jnp.asarray([5]), interpret=True)
    # poison everything past row 5
    kp2 = kp.at[1, 1:].set(99.0).at[2].set(-99.0)
    vp2 = vp.at[1, 1:].set(99.0).at[2].set(-99.0)
    out5b = paged_attention(q, kp2, vp2, tables, jnp.asarray([5]), interpret=True)
    # row 5 = page 1, offset 1 -> that row matters, rows 6+ don't
    kp3 = kp.at[2, 2:].set(99.0)
    vp3 = vp.at[2, 2:].set(99.0)
    out6 = paged_attention(q, kp3, vp3, tables, jnp.asarray([6]), interpret=True)
    ref6 = paged_attention(q, kp, vp, tables, jnp.asarray([6]), interpret=True)
    assert not np.allclose(np.asarray(out5), np.asarray(out5b))  # valid row changed
    np.testing.assert_allclose(np.asarray(out6), np.asarray(ref6))

    # table entries past a lane's last page (stale or trash ids) are fetched
    # with the lane's last block but never attended: poisoning the pages
    # they name leaves the output unchanged
    q, kp, vp, tables, _, _ = _pa_inputs(rng, "whole_table", False)
    ps = kp.shape[1]
    lengths = jnp.asarray([3 * ps + 5], jnp.int32)   # 4 live pages of a block
    out = paged_attention(q, kp, vp, tables, lengths, interpret=True)
    dead = tables[0, 4:]
    kp2, vp2 = kp.at[dead].set(99.0), vp.at[dead].set(-99.0)
    out2 = paged_attention(q, kp2, vp2, tables, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
