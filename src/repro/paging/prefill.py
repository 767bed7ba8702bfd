"""Chunked prefill over the paged cache: admit a long prompt in page-sized
chunks interleaved with decode steps.

One chunk step embeds ``chunk_len`` prompt tokens at absolute offset
``start``, runs them through the stack — each attention block writes the
chunk's K/V (or MLA latents) into the lane's pages and attends the
gathered prefix + chunk under the ordinary causal mask — and returns the
sampled token for the chunk's last valid row (only the final chunk's
sample is used).  Because the bf16 cache roundtrip is lossless and every
per-row computation is position-independent, the chunked admission is
bitwise the unchunked prefill (see ``models/attention.attention_chunk``);
the engine's exact-match tests pin that down.

Chunkable kinds come in two tiers:

* ``chunkable`` — the attention family whose math is strictly
  row-independent: ``attn`` (incl. the MLA rewrite) and dense FFN layers.
  Chunking is *bitwise* the unchunked prefill; prefix caching and
  speculative verify require exactly this contract.
* ``chunkable_with_state`` — additionally the recurrent kinds
  (``rglru``/``mlstm``/``slstm``), whose cells carry their state across
  chunk boundaries (``models/recurrent.*_chunk``): pad rows are
  neutralized in each cell's own algebra (identity recurrence / zero
  gate injection / carry freeze), so the carried state is exact and
  chunk-boundary placement only reorders float reductions (sLSTM is
  bitwise; RG-LRU/mLSTM are allclose — the associative/chunk scans
  regroup).  This is what lets the engine chunk-admit xLSTM-style
  stacks instead of forcing exact-length one-shot admissions.

Excluded by construction:

* ``moe`` — expert capacity is ``ceil(S * k / E * cf)``: it depends on how
  many tokens share the dispatch, so chunking would change which tokens
  drop and break output-invisibility;
* ``local_attn`` — the ring buffer is written modulo the window, which a
  partial chunk would wrap incorrectly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import recurrent as rec
from repro.models import transformer as tfm
from repro.models.layers import embed, glu_mlp, rmsnorm, unembed

CHUNKABLE_KINDS = frozenset({"attn", "mla", "dense_ffn_layer"})
# state-carrying kinds the chunk step can ALSO run (see module docstring
# for the weaker exactness contract)
STATEFUL_CHUNK_KINDS = frozenset({"rglru", "mlstm", "slstm"})


def stack_kinds(cfg: ModelConfig) -> frozenset[str]:
    """Effective block kinds across the WHOLE stack (lead dense layers +
    scanned periods + tail remainder) — the one place layout-derived kind
    sets come from, shared by the engine's paged-pool detection and the
    chunkability check below."""
    lead, n_periods, tail_kinds = tfm.layer_layout(cfg)
    kinds = {"dense_ffn_layer"} if lead else set()
    if n_periods:
        kinds |= {tfm.effective_kind(k, cfg) for k in cfg.block_pattern}
    kinds |= {tfm.effective_kind(k, cfg) for k in tail_kinds}
    return frozenset(kinds)


def chunkable(cfg: ModelConfig) -> bool:
    """Can this stack prefill in chunks *bitwise-identically* to the
    unchunked prefill?  (The contract prefix caching and speculative
    verify require.)"""
    if cfg.is_encoder_decoder or cfg.frontend is not None:
        return False
    return stack_kinds(cfg) <= CHUNKABLE_KINDS


def chunkable_with_state(cfg: ModelConfig) -> bool:
    """Can this stack prefill in chunks at all — allowing state-carrying
    recurrent cells whose chunk boundaries regroup float reductions
    (token-equivalent, not bitwise)?  This is the engine's prefill_chunk
    gate; the stricter :func:`chunkable` still gates prefix/spec."""
    if cfg.is_encoder_decoder or cfg.frontend is not None:
        return False
    return stack_kinds(cfg) <= (CHUNKABLE_KINDS | STATEFUL_CHUNK_KINDS)


def _lane_state(cache, lane, start):
    """Slice lane ``lane``'s per-lane state leaves (axis 0), zeroed for
    the first chunk — a freed lane's leaves hold the previous occupant's
    stale state, which admission must not integrate."""
    st = jax.tree_util.tree_map(
        lambda v: jax.lax.dynamic_slice_in_dim(v, lane, 1, axis=0), cache)
    return jax.tree_util.tree_map(
        lambda v: jnp.where(start[0] == 0, jnp.zeros_like(v), v), st)


def _lane_state_update(cache, new_state, lane):
    """Write the (1, ...) state back into lane ``lane`` of every leaf."""
    return jax.tree_util.tree_map(
        lambda full, part: jax.lax.dynamic_update_slice(
            full, part.astype(full.dtype),
            (jnp.asarray(lane, jnp.int32),) + (0,) * (full.ndim - 1)),
        cache, new_state)


def _apply_block_chunk(x, p, kind: str, cfg: ModelConfig, cache, table_row,
                       lane, start, true_len, positions, layer=None):
    """One block over a (1, C, d) chunk against the paged cache (``layer``:
    the block's layer in the scan's carried pools, None unscanned)."""
    kind = tfm.effective_kind(kind, cfg)
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if kind in STATEFUL_CHUNK_KINDS:
        # recurrent cells: state lives per-lane (axis 0 — scan already
        # peeled the stacked periods axis), carried chunk to chunk
        cell = {"rglru": rec.rglru_chunk, "mlstm": rec.mlstm_chunk,
                "slstm": rec.slstm_chunk}[kind]
        a, new_state = cell(h, p["cell"], cfg,
                            _lane_state(cache, lane, start), true_len[0])
        x = x + a
        cache = _lane_state_update(cache, new_state, lane)
        if kind == "rglru":  # rglru blocks carry their own norm2+MLP;
            h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)  # xLSTM blocks don't
            x = x + glu_mlp(h2, p["mlp"], cfg.act, cfg.quant_mode,
                            backend=cfg.gemm_backend)
        return x, cache
    if kind not in CHUNKABLE_KINDS:
        raise ValueError(f"block kind {kind!r} is not chunkable")
    chunk = attn.mla_chunk if cfg.uses_mla(kind) else attn.attention_chunk
    a, cache = tfm.on_layer(
        lambda c: chunk(h, p["attn"], cfg, c, table_row, start,
                        positions=positions), cache, layer)
    x = x + a
    h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
    x = x + glu_mlp(h2, p["mlp"], cfg.act, cfg.quant_mode,
                    backend=cfg.gemm_backend)
    return x, cache


def make_chunk_step(cfg: ModelConfig, chunk_len: int):
    """Build the jittable chunk step.

    chunk_step(params, cache, tokens, lane, start, true_len)
        -> (last-valid-row logits (1, V), new cache)

    ``tokens``: (1, chunk_len) right-padded; ``start``: (1,) absolute
    position of the chunk's first token; ``true_len``: (1,) valid tokens
    in this chunk.  Padded tail rows write garbage pages that the next
    chunk (or the first decode step) overwrites before any query can
    attend them — the same argument that makes bucketed prefill exact.
    ``cache["pos"]`` for the lane is set to ``start + true_len`` so the
    final chunk leaves the lane decode-ready.
    """
    if not chunkable_with_state(cfg):
        raise ValueError(
            f"{cfg.name}: stack has non-chunkable kinds "
            f"{sorted(stack_kinds(cfg) - CHUNKABLE_KINDS - STATEFUL_CHUNK_KINDS)}")

    lead, n_periods, tail_kinds = tfm.layer_layout(cfg)

    def chunk_step(params, cache, tokens, lane, start, true_len):
        x = embed(tokens, params["embed"])
        positions = start[:, None] + jnp.arange(chunk_len, dtype=jnp.int32)[None, :]
        tables = cache["block_tables"]
        table_row = jax.lax.dynamic_slice(
            tables, (lane, 0), (1, tables.shape[1]))

        new_cache = dict(cache)
        new_cache["head_blocks"] = list(cache["head_blocks"])
        for i, p in enumerate(params.get("head_blocks", [])):
            x, c = _apply_block_chunk(x, p, "dense_ffn_layer", cfg,
                                      cache["head_blocks"][i], table_row,
                                      lane, start, true_len, positions)
            new_cache["head_blocks"][i] = c

        if params.get("blocks", ()):
            def period(h, slot_params, slot_cache, layer):
                out = []
                for s, kind in enumerate(cfg.block_pattern):
                    h, c = _apply_block_chunk(h, slot_params[s], kind, cfg,
                                              slot_cache[s], table_row,
                                              lane, start, true_len,
                                              positions, layer)
                    out.append(c)
                return h, tuple(out)

            x, new_cache["blocks"] = tfm.scan_pools(
                period, x, params["blocks"], cache["blocks"], cfg.scan_unroll)

        new_cache["tail_blocks"] = list(cache["tail_blocks"])
        for i, p in enumerate(params.get("tail_blocks", [])):
            x, c = _apply_block_chunk(x, p, tail_kinds[i], cfg,
                                      cache["tail_blocks"][i], table_row,
                                      lane, start, true_len, positions)
            new_cache["tail_blocks"][i] = c

        new_cache["pos"] = cache["pos"].at[lane].set(
            (start[0] + true_len[0]).astype(jnp.int32))

        idx = jnp.clip(true_len - 1, 0, chunk_len - 1)          # (1,)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        h = rmsnorm(x_last, params["final_norm"], cfg.norm_eps)
        table = params["embed"] if cfg.tie_embeddings else params["head"]
        logits = unembed(h, table)[:, 0, :]
        return logits, new_cache

    return chunk_step
