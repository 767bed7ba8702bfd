"""Decode-time caches for every block kind.

Cache pytrees mirror the parameter layout (per-slot stacked along the
scanned period axis) so ``lax.scan`` can thread them through the stack:

* ``attn``       -> {"k","v"}: (B, S_cache, H_kv, D); local_attn uses a
                    ring buffer of S_cache == window (O(1) memory at 500k).
* ``mla``        -> {"ckv","kr"}: compressed latent cache (the MLA win);
                    an MLA model's ``moe`` / ``dense_ffn_layer`` too.
* ``rglru``      -> {"h","conv"}: O(1) recurrent state.
* ``mlstm``      -> {"C","n"}: matrix memory, O(1) in sequence length.
* ``slstm``      -> {"c","n","h"}.

``paged_block_cache_shape`` gives the paged layout (repro/paging/): the
same payloads re-cut into a global ``(n_pages, page_size, ...)`` pool that
per-lane block tables index, for kinds whose cache grows with sequence
length; O(1)/O(window) kinds keep the per-lane layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import COMPUTE_DTYPE


def block_cache_shape(kind: str, cfg: ModelConfig, batch: int, cache_len: int):
    """ShapeDtypeStructs for one layer's cache of the given kind (an MLA
    model's MoE and dense blocks cache latents, as its ``mla`` blocks do)."""
    kind = "mla" if cfg.uses_mla(kind) else kind
    hd = cfg.resolved_head_dim
    f32 = jnp.float32
    if kind in ("attn", "local_attn", "moe", "dense_ffn_layer"):
        s = cache_len
        if kind == "local_attn" and cfg.sliding_window is not None:
            s = min(cache_len, cfg.sliding_window)
        shp = (batch, s, cfg.n_kv_heads, hd)
        if cfg.kv_cache_dtype == "int8":
            # SPOGA-style byte-size storage: int8 payload + per-(pos, head)
            # scale — halves the dominant HBM stream of long-context decode.
            return {
                "k": jax.ShapeDtypeStruct(shp, jnp.int8),
                "v": jax.ShapeDtypeStruct(shp, jnp.int8),
                "k_scale": jax.ShapeDtypeStruct(shp[:3], jnp.float32),
                "v_scale": jax.ShapeDtypeStruct(shp[:3], jnp.float32),
            }
        return {
            "k": jax.ShapeDtypeStruct(shp, COMPUTE_DTYPE),
            "v": jax.ShapeDtypeStruct(shp, COMPUTE_DTYPE),
        }
    if kind == "mla":
        m = cfg.mla
        return {
            "ckv": jax.ShapeDtypeStruct((batch, cache_len, m.kv_lora_rank), COMPUTE_DTYPE),
            "kr": jax.ShapeDtypeStruct((batch, cache_len, m.qk_rope_head_dim), COMPUTE_DTYPE),
        }
    if kind == "rglru":
        lru = cfg.lru_width or cfg.d_model
        return {
            "h": jax.ShapeDtypeStruct((batch, lru), f32),
            "conv": jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, lru), f32),
        }
    if kind == "mlstm":
        dh = cfg.d_model // cfg.n_heads
        return {
            "C": jax.ShapeDtypeStruct((batch, cfg.n_heads, dh, dh), f32),
            "n": jax.ShapeDtypeStruct((batch, cfg.n_heads, dh), f32),
        }
    if kind == "slstm":
        dh = cfg.d_model // cfg.n_heads
        s = jax.ShapeDtypeStruct((batch, cfg.n_heads, dh), f32)
        return {"c": s, "n": s, "h": s}
    raise ValueError(f"no cache for block kind {kind!r}")


def paged_block_cache_shape(kind: str, cfg: ModelConfig, batch: int,
                            cache_len: int, n_pages: int, page_size: int):
    """ShapeDtypeStructs for one layer's *paged* cache of the given kind.

    Attention-family kinds store KV in a global page pool shared by every
    lane — ``(n_pages, page_size, H_kv, D)`` payloads (``kp``/``vp``, plus
    ``kp_scale``/``vp_scale`` planes for the int8 byte-size variant) indexed
    through per-lane block tables.  MLA pages hold the compressed latents
    (``ckvp``/``krp``).  Kinds whose state is already O(1) or O(window) per
    lane keep their per-lane layout from :func:`block_cache_shape`; an MLA
    model's MoE and dense blocks page latents too:

    * recurrent state (rglru/mlstm/slstm) — nothing to page;
    * local_attn ring buffers — a window-sized ring is its own best
      packing; paging it would only re-introduce indirection.
    """
    kind = "mla" if cfg.uses_mla(kind) else kind
    hd = cfg.resolved_head_dim
    if kind in ("attn", "moe", "dense_ffn_layer") or (
        kind == "local_attn" and cfg.sliding_window is None
    ):
        shp = (n_pages, page_size, cfg.n_kv_heads, hd)
        if cfg.kv_cache_dtype == "int8":
            return {
                "kp": jax.ShapeDtypeStruct(shp, jnp.int8),
                "vp": jax.ShapeDtypeStruct(shp, jnp.int8),
                "kp_scale": jax.ShapeDtypeStruct(shp[:3], jnp.float32),
                "vp_scale": jax.ShapeDtypeStruct(shp[:3], jnp.float32),
            }
        return {
            "kp": jax.ShapeDtypeStruct(shp, COMPUTE_DTYPE),
            "vp": jax.ShapeDtypeStruct(shp, COMPUTE_DTYPE),
        }
    if kind == "mla":
        m = cfg.mla
        return {
            "ckvp": jax.ShapeDtypeStruct(
                (n_pages, page_size, m.kv_lora_rank), COMPUTE_DTYPE),
            "krp": jax.ShapeDtypeStruct(
                (n_pages, page_size, m.qk_rope_head_dim), COMPUTE_DTYPE),
        }
    # per-lane kinds ride the slot layout unchanged
    return block_cache_shape(kind, cfg, batch, cache_len)


def zeros_like_shapes(tree):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), tree)
