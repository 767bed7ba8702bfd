"""Device-side paged KV cache: page pools + traceable scatter/insert.

``PagedCache`` is the paged counterpart of ``serving.slots.SlotCache``:
one pool pytree allocated once (built from
``models/model.paged_cache_shapes``), with attention-family KV in global
pools of ``n_pages`` pages (``models/kvcache.paged_block_cache_shape``; a
scanned stack's pools carry a leading layer axis) and per-lane state
(recurrent cells, local-attention rings, ``pos``) in lane-indexed leaves.
Host bookkeeping lives in ``manager.PageManager``; the block table is the
only host-mutated array the jitted decode step reads.

``paged_insert`` is traceable so the engine can fuse
prefill + first-token sample + page scatter into ONE dispatch, exactly
like the slot engine's fused admission.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, default_page_count, pages_for
from repro.models import model as model_lib
from repro.models.kvcache import (
    is_paged, page_size_of, pool_rows, to_pages, zeros_like_shapes)
from repro.paging.manager import PageManager


def _lane_update(full, part, lane, axis):
    """Write the batch=1 ``part`` into lane ``lane`` of ``full`` (per-lane
    leaves: recurrent state, local-attn rings)."""
    starts = tuple(
        jnp.asarray(lane, jnp.int32) if i == axis else 0
        for i in range(full.ndim)
    )
    return jax.lax.dynamic_update_slice(full, part.astype(full.dtype), starts)


def _batch_row(part, row: int, axis: int):
    """Row ``row`` of a batch=k leaf, keeping a size-1 batch axis."""
    idx = [slice(None)] * part.ndim
    idx[axis] = slice(row, row + 1)
    return part[tuple(idx)]


def _scatter_block(pool_blk, single_blk, lane, page_ids, stacked: bool,
                   src_row: int = 0):
    """Insert one layer(-stack)'s prefill cache: paged dicts scatter whole
    pages, per-lane dicts scatter the lane row.  ``stacked`` marks leaves
    with a leading scanned-period axis; ``src_row`` picks the batch row of
    a batch=k prefill cache (stacked admissions scatter one row per lane)."""
    if is_paged(pool_blk):
        out, ps = {}, page_size_of(pool_blk)
        for pk, leaf in pool_blk.items():
            rows = pool_rows(pk, single_blk)          # ([periods,] batch, S, ...)
            rows = rows[:, src_row] if stacked else rows[src_row]
            pages = to_pages(pk, rows, ps)
            idx = (slice(None), page_ids) if stacked else page_ids
            out[pk] = leaf.at[idx].set(pages.astype(leaf.dtype))
        return out
    axis = 1 if stacked else 0
    return jax.tree_util.tree_map(
        lambda full, part: _lane_update(full, _batch_row(part, src_row, axis),
                                        lane, axis),
        pool_blk, single_blk)


def paged_insert(cache, single, lane, page_ids, table_row, new_len):
    """Scatter a batch=1 contiguous prefill cache into the page pools.

    ``single`` must hold exactly ``len(page_ids) * page_size`` cache rows
    (the engine sizes the admission prefill that way); ``table_row`` is the
    lane's full (max_pages,) block-table row, written to the device table
    in the same dispatch.  Traceable — the engine fuses it into admission.
    """
    new = dict(cache)
    new["pos"] = cache["pos"].at[lane].set(new_len.astype(jnp.int32))
    new["block_tables"] = cache["block_tables"].at[lane].set(table_row)
    new["head_blocks"] = [
        _scatter_block(pb, sb, lane, page_ids, stacked=False)
        for pb, sb in zip(cache["head_blocks"], single["head_blocks"])
    ]
    new["blocks"] = tuple(
        _scatter_block(pb, sb, lane, page_ids, stacked=True)
        for pb, sb in zip(cache["blocks"], single["blocks"])
    )
    new["tail_blocks"] = [
        _scatter_block(pb, sb, lane, page_ids, stacked=False)
        for pb, sb in zip(cache["tail_blocks"], single["tail_blocks"])
    ]
    return new


def paged_insert_many(cache, multi, lanes, page_ids, table_rows, new_lens,
                      k: int):
    """Scatter a batch=``k`` prefill cache into ``k`` lanes' pages — the
    stacked-admission counterpart of :func:`paged_insert` (same-bucket
    prompts share ONE prefill dispatch; each batch row lands in its own
    lane's pages).  ``page_ids``: (k, n_pages_per_lane); ``table_rows``:
    (k, max_pages); ``new_lens``: (k,).  ``k`` is static (trace key), so
    the loop unrolls.  Traceable — the engine fuses it into its batched
    paged admission."""
    new = dict(cache)
    pos, tables = cache["pos"], cache["block_tables"]
    for i in range(k):
        pos = pos.at[lanes[i]].set(new_lens[i].astype(jnp.int32))
        tables = tables.at[lanes[i]].set(table_rows[i])
    new["pos"], new["block_tables"] = pos, tables

    def scatter_group(pool_blocks, multi_blocks, stacked):
        out = []
        for pb, mb in zip(pool_blocks, multi_blocks):
            for i in range(k):
                pb = _scatter_block(pb, mb, lanes[i], page_ids[i], stacked,
                                    src_row=i)
            out.append(pb)
        return out

    new["head_blocks"] = scatter_group(cache["head_blocks"],
                                       multi["head_blocks"], stacked=False)
    new["blocks"] = tuple(scatter_group(cache["blocks"], multi["blocks"],
                                        stacked=True))
    new["tail_blocks"] = scatter_group(cache["tail_blocks"],
                                       multi["tail_blocks"], stacked=False)
    return new


# module-level jit shared across engine instances (mirrors slots._scatter_lane)
_paged_insert = jax.jit(paged_insert, donate_argnums=(0,))


def _move_pages_block(blk, src, dst, stacked: bool):
    if not is_paged(blk):
        return blk
    if stacked:
        return {k: leaf.at[:, dst].set(leaf[:, src]) for k, leaf in blk.items()}
    return {k: leaf.at[dst].set(leaf[src]) for k, leaf in blk.items()}


def _move_pages(cache, src, dst):
    """Copy pool pages ``src -> dst`` in every layer (defrag compaction)."""
    new = dict(cache)
    new["head_blocks"] = [_move_pages_block(b, src, dst, False)
                          for b in cache["head_blocks"]]
    new["blocks"] = tuple(_move_pages_block(b, src, dst, True)
                          for b in cache["blocks"])
    new["tail_blocks"] = [_move_pages_block(b, src, dst, False)
                          for b in cache["tail_blocks"]]
    return new


_move_pages_jit = jax.jit(_move_pages, donate_argnums=(0,))


class PagedCache:
    """Engine-owned paged pool: ``n_lanes`` block-table rows over
    ``n_pages`` physical pages of ``page_size`` rows each."""

    def __init__(self, cfg: ModelConfig, n_lanes: int, cache_len: int,
                 page_size: int, n_pages: int | None = None, mesh=None):
        self.n_lanes = n_lanes
        self.cache_len = cache_len
        self.page_size = page_size
        self.max_pages = pages_for(cache_len, page_size)
        self.n_pages = (default_page_count(n_lanes, cache_len, page_size)
                        if n_pages is None else n_pages)
        shapes = model_lib.paged_cache_shapes(
            cfg, n_lanes, cache_len, page_size, self.n_pages)
        self.mesh = mesh
        self._table_sharding = None
        if mesh is not None:
            # sharded serving: commit the pools to their TP layout (KV
            # heads over "model", block tables + pos replicated — see
            # runtime/sharding.pool_specs).  Committing here, once, means
            # every later jit (admission, decode, insert, defrag moves)
            # inherits the layout through donation instead of re-deciding
            # it; the host-side PageManager stays the single block-table
            # owner and its uploads re-commit to the replicated sharding
            # so the decode program never changes between steps.
            from repro.runtime.sharding import named, pool_specs

            shardings = named(pool_specs(shapes, mesh, cfg.n_kv_heads), mesh)
            self.cache = jax.device_put(zeros_like_shapes(shapes), shardings)
            self._table_sharding = shardings["block_tables"]
        else:
            self.cache = zeros_like_shapes(shapes)
        self.manager = PageManager(self.n_pages, page_size, n_lanes,
                                   self.max_pages)

    def insert(self, single_cache, lane: int, page_ids, new_len) -> None:
        """Standalone (non-fused) insert — tests and defrag verification;
        the engine uses the traceable ``paged_insert`` inside its fused
        admission jit instead."""
        self.cache = _paged_insert(
            self.cache, single_cache, jnp.int32(lane),
            jnp.asarray(page_ids, jnp.int32),
            jnp.asarray(self.manager.block_tables[lane]),
            jnp.asarray(new_len, jnp.int32))

    def sync_tables(self) -> None:
        """Upload the host block table if growth/free/defrag changed it."""
        if self.manager.dirty:
            tables = jnp.asarray(self.manager.block_tables)
            if self._table_sharding is not None:
                # keep the upload committed-replicated: a mix of committed
                # and uncommitted table inputs would give the decode jit
                # two distinct input shardings (two compiles) for one
                # logical program
                tables = jax.device_put(tables, self._table_sharding)
            self.cache = {**self.cache, "block_tables": tables}
            self.manager.dirty = False

    def free(self, lane: int) -> int:
        """Release a lane's pages back to the pool (same step)."""
        n = self.manager.free_lane(lane)
        return n

    def copy_pages(self, src, dst) -> None:
        """Duplicate pool pages ``src -> dst`` in every layer (CoW fork:
        the source keeps its bytes for the other holders; the destination
        becomes the forking lane's private copy).  Reuses the defrag move
        kernel — a move IS a copy that leaves the source untouched."""
        self.cache = _move_pages_jit(
            self.cache, jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))
        self.sync_tables()

    def defrag(self) -> list:
        """Compact the pool; returns the ``(src, dst)`` move pairs applied
        (the flight recorder journals them as the defrag's operands)."""
        moves = self.manager.defrag()
        if moves:
            src = jnp.asarray([s for s, _ in moves], jnp.int32)
            dst = jnp.asarray([d for _, d in moves], jnp.int32)
            self.cache = _move_pages_jit(self.cache, src, dst)
            self.sync_tables()
        return moves

    @property
    def pos(self):
        return self.cache["pos"]
