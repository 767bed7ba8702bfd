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
same payloads re-cut into a global pool of ``n_pages`` pages that per-lane
block tables index, for kinds whose cache grows with sequence length;
O(1)/O(window) kinds keep the per-lane layout.  :func:`to_pages` and
:func:`from_pages` convert between the two.
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


# paged-pool leaf -> the slot-cache leaves holding the same rows (the int8
# scale pool holds K's and V's); every leaf indexed through ``block_tables``
POOL_KEYS = {
    "kp": ("k",), "vp": ("v",), "kvp_scale": ("k_scale", "v_scale"),
    "ckvp": ("ckv",), "krp": ("kr",),
}


def is_paged(cache) -> bool:
    """Does this layer's cache hold page pools (GQA K/V or MLA latents)?"""
    return "kp" in cache or "ckvp" in cache


def page_size_of(cache) -> int:
    """Rows a page of this layer's pools holds."""
    return cache["kp" if "kp" in cache else "ckvp"].shape[-2]


def paged_block_cache_shape(kind: str, cfg: ModelConfig, batch: int,
                            cache_len: int, n_pages: int, page_size: int):
    """ShapeDtypeStructs for one layer's *paged* cache of the given kind.

    Attention-family kinds store KV in a global page pool shared by every
    lane, indexed through per-lane block tables.  Pages are lane-dense, so
    that the device's default layout of each pool is row-major (a minor dim
    narrower than a lane row — ``D`` = 64, ``H_kv`` = 8, a 64-wide rope key
    — gets a pages-minor default layout, which the attention kernel and an
    in-place layer scan would pay a relayout of the whole pool for):

    * ``kp``/``vp`` ``(n_pages, page_size, H_kv * D)``, heads side by side;
    * ``kvp_scale`` (int8 byte-size variant) ``(n_pages, 2, H_kv *
      page_size)``, a page's K scales and V scales, each row head-major
      (two rows: one row alone would take a ``(1, 128)`` tiling, which
      the device's scatter cannot write in place);
    * MLA latents ``ckvp`` ``(n_pages, page_size, kv_lora_rank)`` and rope
      keys ``krp`` ``(n_pages, page_size * rope_dim)``.

    Kinds whose state is already O(1) or O(window) per lane keep their
    per-lane layout from :func:`block_cache_shape`; an MLA model's MoE and
    dense blocks page latents too:

    * recurrent state (rglru/mlstm/slstm) — nothing to page;
    * local_attn ring buffers — a window-sized ring is its own best
      packing; paging it would only re-introduce indirection.
    """
    kind = "mla" if cfg.uses_mla(kind) else kind
    hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    if kind in ("attn", "moe", "dense_ffn_layer") or (
        kind == "local_attn" and cfg.sliding_window is None
    ):
        shp = (n_pages, page_size, hkv * hd)
        if cfg.kv_cache_dtype == "int8":
            return {
                "kp": jax.ShapeDtypeStruct(shp, jnp.int8),
                "vp": jax.ShapeDtypeStruct(shp, jnp.int8),
                "kvp_scale": jax.ShapeDtypeStruct(
                    (n_pages, 2, hkv * page_size), jnp.float32),
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
                (n_pages, page_size * m.qk_rope_head_dim), COMPUTE_DTYPE),
        }
    # per-lane kinds ride the slot layout unchanged
    return block_cache_shape(kind, cfg, batch, cache_len)


def pool_rows(key: str, slot_cache):
    """The slot-layout rows ``(..., S, *row)`` pool leaf ``key`` holds, from
    a slot cache's leaves: the scale pool's row is ``(2, H)``, K's scales
    then V's."""
    rows = [slot_cache[k] for k in POOL_KEYS[key]]
    return jnp.stack(rows, axis=-2) if len(rows) > 1 else rows[0]


def to_pages(key: str, rows, page_size: int):
    """Slot-layout rows ``(..., S, *row)`` of pool leaf ``key`` (see
    :func:`pool_rows`) as whole pages ``(..., S // page_size, *page)`` of the
    paged layout above."""
    n = 1 if key in ("ckvp", "krp") else 2                 # dims of a row
    lead, s = rows.shape[:-n - 1], rows.shape[-n - 1]
    pages = rows.reshape(lead + (s // page_size, page_size) + rows.shape[-n:])
    if key == "kvp_scale":   # (.., ps, 2, H) -> (.., 2, H * ps)
        return jnp.moveaxis(pages, -3, -1).reshape(pages.shape[:-3] + (2, -1))
    if key == "ckvp":
        return pages
    # K/V (.., ps, H, D) -> (.., ps, H * D); rope keys (.., ps, r) -> (.., ps * r)
    return pages.reshape(pages.shape[:-2] + (-1,))


def from_pages(key: str, pages, page_size: int):
    """Whole pages ``(..., P, *page)`` of pool leaf ``key`` as rows
    ``(..., P * page_size, *row)``: K/V rows ``H * D`` wide, scale rows
    ``(2, H)``, latents and rope keys their own width."""
    if key == "kvp_scale":   # (.., 2, H * ps) -> (.., ps, 2, H)
        lead = pages.shape[:-3]
        pages = jnp.moveaxis(
            pages.reshape(pages.shape[:-1] + (-1, page_size)), -1, -3)
        return pages.reshape(lead + (pages.shape[-4] * page_size,) + pages.shape[-2:])
    n = 1 if key == "krp" else 2                           # dims of a page
    lead, p = pages.shape[:-n - 1], pages.shape[-n - 1]
    return pages.reshape(lead + (p * page_size, -1))


def zeros_like_shapes(tree):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), tree)
