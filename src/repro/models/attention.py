"""Attention variants: GQA/MQA/MHA, sliding-window, MLA, cross-attention.

Long sequences use a query-chunked exact attention (``lax.scan`` over query
blocks, fp32 softmax) so no S x S score matrix is ever materialized — the
XLA-friendly equivalent of a flash kernel, used by both the CPU dry-run and
as the reference for any future fused TPU attention kernel.  Local
(sliding-window) blocks additionally slice only the KV band each chunk
needs, making them O(S * window).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.kvcache import from_pages, page_size_of
from repro.models.layers import apply_rope, init_linear, linear, rmsnorm, yarn_mscale

NEG_INF = -1e30


def _pick_chunk(s: int) -> int:
    for c in (512, 256, 128, 64):
        if s % c == 0 and s > c:
            return c
    return s


# ---------------------------------------------------------------------------
# Standard (GQA) attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": init_linear(k1, d, cfg.n_heads * hd),
        "wk": init_linear(k2, d, cfg.n_kv_heads * hd),
        "wv": init_linear(k3, d, cfg.n_kv_heads * hd),
        "wo": init_linear(k4, cfg.n_heads * hd, d),
    }


def _attend_chunk(q, k, v, q_offset, kv_offset, causal, window, scale=None):
    """q: (B, C, G, Hkv, D); k/v: (B, S, Hkv, D). Exact fp32 softmax;
    scores scaled by ``scale`` (``D ** -0.5`` if None)."""
    d = q.shape[-1]
    # bf16 operands, f32 accumulation: never materializes an f32 copy of
    # the (B, S, H, D) keys (at 32k-decode that copy alone is GiBs/device).
    scores = jnp.einsum("bcghd,bshd->bcghs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (d ** -0.5 if scale is None else scale)
    qpos = q_offset + jnp.arange(q.shape[1])[:, None]
    kpos = kv_offset + jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bcghs,bshd->bcghd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def multihead_attention(q, k, v, *, causal=True, window=None, scale=None):
    """q: (B, Sq, Hq, D), k/v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D).

    Query-chunked, memory O(C x Skv); local attention slices the KV band.
    ``scale`` multiplies the scores (``D ** -0.5`` if None).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # may differ from d (MLA: qk_dim != v_head_dim)
    g = hq // hkv
    qg = q.reshape(b, sq, g, hkv, d)
    chunk = _pick_chunk(sq)
    if chunk == sq:
        out = _attend_chunk(qg, k, v, 0, 0, causal, window, scale)
        return out.reshape(b, sq, hq, dv)

    n_chunks = sq // chunk
    qs = qg.reshape(b, n_chunks, chunk, g, hkv, d).transpose(1, 0, 2, 3, 4, 5)

    # NB: chunk bodies are rematerialized — without this, the scan's backward
    # saves every chunk's softmax probs, i.e. the full S x S score matrix.
    if window is not None and sq == skv:
        # Local attention: each chunk only needs KV in [start-window, start+chunk).
        band = window + chunk

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def body(_, args):
            qc, idx = args
            start = jnp.maximum(idx * chunk - window, 0)
            # clamp so the static-size band stays in bounds
            start = jnp.minimum(start, skv - band) if skv >= band else 0
            kc = jax.lax.dynamic_slice_in_dim(k, start, min(band, skv), axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, min(band, skv), axis=1)
            out = _attend_chunk(qc, kc, vc, idx * chunk, start, causal, window, scale)
            return None, out

        _, outs = jax.lax.scan(body, None, (qs, jnp.arange(n_chunks)))
    else:

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def body(_, args):
            qc, idx = args
            out = _attend_chunk(qc, k, v, idx * chunk, 0, causal, window, scale)
            return None, out

        _, outs = jax.lax.scan(body, None, (qs, jnp.arange(n_chunks)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hq, dv)
    return out


def _constrain_heads(t):
    """Pin (B, S, H, D) to batch-over-DP x heads-over-"model" (Megatron TP).

    Without the explicit constraint the partitioner reshards the chunked
    attention's 6-D reshapes through an 'involuntary full
    rematerialization' (replicate-then-repartition) path.  Identity outside
    a mesh; dims that don't divide are replicated."""
    from jax.sharding import PartitionSpec as P

    from repro.runtime.sharding import ambient_mesh, dp_axis_for, model_axis_for

    m = ambient_mesh()
    if m is None or t.ndim != 4:
        return t
    return jax.lax.with_sharding_constraint(
        t, P(dp_axis_for(m, t.shape[0]), None, model_axis_for(m, t.shape[2]),
             None))


def attention_block(x, p, cfg: ModelConfig, positions, *, causal=True, window=None):
    """Full self-attention over x: projections + RoPE + attend + output."""
    b, s, _ = x.shape
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q = linear(x, p["wq"], qm, be, tp="col").reshape(b, s, hq, hd)
    k = linear(x, p["wk"], qm, be, tp="col").reshape(b, s, hkv, hd)
    v = linear(x, p["wv"], qm, be, tp="col").reshape(b, s, hkv, hd)
    q = _constrain_heads(apply_rope(q, positions, cfg.rope_theta))
    k = _constrain_heads(apply_rope(k, positions, cfg.rope_theta))
    v = _constrain_heads(v)
    out = multihead_attention(q, k, v, causal=causal, window=window)
    return linear(out.reshape(b, s, hq * hd), p["wo"], qm, be, tp="row"), (k, v)


def quantize_kv(t):
    """(B, S, H, D) -> int8 payload + per-(b, s, h) f32 scale (SPOGA-style
    byte-size cache storage; halves decode's dominant HBM stream)."""
    tf = t.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(tf), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(tf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _decode_qkv(x_t, p, cfg: ModelConfig, pos):
    """Shared decode-side projections + RoPE. Returns q, k, v (B, 1, H, D)."""
    b = x_t.shape[0]
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q = linear(x_t, p["wq"], qm, be, tp="col").reshape(b, 1, hq, hd)
    k = linear(x_t, p["wk"], qm, be, tp="col").reshape(b, 1, hkv, hd)
    v = linear(x_t, p["wv"], qm, be, tp="col").reshape(b, 1, hkv, hd)
    posb = pos[:, None]
    q = _constrain_heads(apply_rope(q, posb, cfg.rope_theta))
    k = _constrain_heads(apply_rope(k, posb, cfg.rope_theta))
    return q, k, _constrain_heads(v)


def _verify_qkv(x, p, cfg: ModelConfig, pos):
    """W-row verify-window projections + RoPE. x: (B, W, d); pos: (B,) is
    the window's first absolute position. Returns q, k, v (B, W, H, D)."""
    b, w, _ = x.shape
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q = linear(x, p["wq"], qm, be, tp="col").reshape(b, w, hq, hd)
    k = linear(x, p["wk"], qm, be, tp="col").reshape(b, w, hkv, hd)
    v = linear(x, p["wv"], qm, be, tp="col").reshape(b, w, hkv, hd)
    positions = pos[:, None] + jnp.arange(w)[None, :]
    q = _constrain_heads(apply_rope(q, positions, cfg.rope_theta))
    k = _constrain_heads(apply_rope(k, positions, cfg.rope_theta))
    return q, k, _constrain_heads(v)


def _verify_valid(pos, w, smax):
    """(B, W, S) causal mask for the verify window: query row c of lane b
    sits at absolute position pos[b] + c and may attend kpos <= pos[b] + c
    — causal within the window, full paged/slot history before it."""
    row_pos = pos[:, None] + jnp.arange(w)[None, :]
    return jnp.arange(smax)[None, None, :] <= row_pos[:, :, None]


def _decode_attend(qg, k_cache, v_cache, k_scale, v_scale, valid):
    """Single-token attention math over a logically-contiguous KV view.

    qg: (B, C, G, Hkv, D); k_cache/v_cache: (B, S, Hkv, D) payloads
    (int8 when scales are given); valid: (B, S) bool, or (B, C, S) for a
    per-query-row mask (the speculative verify window, where row c may
    attend one position more than row c-1).  Shared by the slot path and
    the paged jnp twin so the two lower to the same graph — that
    structural identity is what makes paged serving bitwise
    output-invisible when the gathered view matches the slot cache_len.
    """
    hd = qg.shape[-1]
    int8_cache = k_scale is not None
    # int8 payload feeds the dot (fused dequant / MXU int8 path); the
    # per-(pos, head) scale factors out of the D-contraction.
    k_op = k_cache.astype(qg.dtype) if int8_cache else k_cache
    scores = jnp.einsum("bcghd,bshd->bcghs", qg, k_op,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    if int8_cache:
        scores = scores * k_scale.transpose(0, 2, 1)[:, None, None, :, :]
    if valid.ndim == 2:
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    else:
        scores = jnp.where(valid[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if int8_cache:
        probs = probs * v_scale.transpose(0, 2, 1)[:, None, None, :, :]
        v_op = v_cache.astype(qg.dtype)
    else:
        v_op = v_cache
    return jnp.einsum("bcghs,bshd->bcghd", probs.astype(v_op.dtype), v_op,
                      preferred_element_type=jnp.float32)


def attention_decode(x_t, p, cfg: ModelConfig, cache, pos, *, window=None):
    """One-token decode. x_t: (B, 1, d); cache {"k","v"[,"k_scale","v_scale"]}
    payloads (B, Smax, Hkv, D); pos (B,). Returns (out, new cache dict)."""
    b = x_t.shape[0]
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    int8_cache = cfg.kv_cache_dtype == "int8"
    q, k, v = _decode_qkv(x_t, p, cfg, pos)

    k_cache, v_cache = cache["k"], cache["v"]
    smax = k_cache.shape[1]
    if window is not None and smax > window:
        # Ring-buffer local cache: slot = pos % window over a window-sized cache
        raise ValueError("local decode cache must be allocated with Smax == window")
    slot = pos % smax if window is not None else pos

    def upd(c, t, i):
        return jax.vmap(
            lambda cc, tt, ii: jax.lax.dynamic_update_slice_in_dim(cc, tt, ii, axis=0)
        )(c, t, i)

    new_cache = dict(cache)
    k_scale = v_scale = None
    if int8_cache:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache, v_cache = upd(k_cache, kq, slot), upd(v_cache, vq, slot)
        k_scale = upd(cache["k_scale"], ks, slot)
        v_scale = upd(cache["v_scale"], vs, slot)
        new_cache.update(k_scale=k_scale, v_scale=v_scale)
    else:
        k_cache, v_cache = upd(k_cache, k, slot), upd(v_cache, v, slot)
    new_cache.update(k=k_cache, v=v_cache)

    g = hq // hkv
    qg = q.reshape(b, 1, g, hkv, hd)
    kpos = jnp.arange(smax)[None, :]
    if window is not None:
        # Ring cache (smax == window): before the ring wraps only slots
        # <= pos hold data; after wrapping every slot is within the window.
        valid = jnp.where(pos[:, None] >= smax, jnp.ones_like(kpos, bool), kpos <= pos[:, None])
    else:
        valid = kpos <= pos[:, None]
    out = _decode_attend(qg, k_cache, v_cache, k_scale, v_scale, valid)
    out = out.astype(x_t.dtype).reshape(b, 1, hq * hd)
    return linear(out, p["wo"], qm, be, tp="row"), new_cache


def attention_verify(x, p, cfg: ModelConfig, cache, pos):
    """W-token speculative verify over a slot cache.

    x: (B, W, d) — the last accepted token plus the drafted window; pos:
    (B,) absolute position of the window's first row.  Writes all W K/V
    rows at pos..pos+W-1 (rows past the eventually-accepted prefix are
    garbage, but the next verify/decode step overwrites them before any
    query can attend them — the same argument that keeps chunked-prefill
    padding output-invisible), then attends the slot history under the
    per-row causal mask.  Row-for-row this lowers to the same dot products
    as W sequential :func:`attention_decode` calls, which is what makes
    greedy speculative output bitwise identical to plain decode.
    Windowed (ring-buffer) local caches are unsupported here — the spec
    stack gates on ``chunkable(cfg)``.
    """
    b, w, _ = x.shape
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    int8_cache = cfg.kv_cache_dtype == "int8"
    q, k, v = _verify_qkv(x, p, cfg, pos)

    k_cache, v_cache = cache["k"], cache["v"]
    smax = k_cache.shape[1]

    def upd(c, t, i):
        return jax.vmap(
            lambda cc, tt, ii: jax.lax.dynamic_update_slice_in_dim(cc, tt, ii, axis=0)
        )(c, t, i)

    new_cache = dict(cache)
    k_scale = v_scale = None
    if int8_cache:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache, v_cache = upd(k_cache, kq, pos), upd(v_cache, vq, pos)
        k_scale = upd(cache["k_scale"], ks, pos)
        v_scale = upd(cache["v_scale"], vs, pos)
        new_cache.update(k_scale=k_scale, v_scale=v_scale)
    else:
        k_cache, v_cache = upd(k_cache, k, pos), upd(v_cache, v, pos)
    new_cache.update(k=k_cache, v=v_cache)

    qg = q.reshape(b, w, hq // hkv, hkv, hd)
    valid = _verify_valid(pos, w, smax)
    out = _decode_attend(qg, k_cache, v_cache, k_scale, v_scale, valid)
    out = out.astype(x.dtype).reshape(b, w, hq * hd)
    return linear(out, p["wo"], qm, be, tp="row"), new_cache


# ---------------------------------------------------------------------------
# Paged attention (block-table KV cache; repro/paging/)
# ---------------------------------------------------------------------------

def _resolve_paged_impl(cfg: ModelConfig) -> str:
    on_tpu = jax.default_backend() == "tpu"
    impl = cfg.paged_attn_impl or ("pallas" if on_tpu else "jnp")
    if impl == "pallas" and not on_tpu:
        raise RuntimeError(
            "paged_attn_impl='pallas' compiles a Pallas TPU kernel, but JAX's "
            f"default backend is {jax.default_backend()!r}; use "
            "'pallas_interpret' (the kernel body in the interpreter) or 'jnp'")
    return impl


def _write_page(tables, pos, page_size, active):
    """(physical page, in-page offset) each lane's next token writes to.

    Inactive lanes are redirected to the reserved trash page 0: unlike the
    slot cache, a lane's pages return to the shared pool on eviction, so a
    garbage write through a stale table entry would corrupt whichever
    request owns that page now.
    """
    pg = jnp.take_along_axis(tables, (pos // page_size)[:, None], axis=1,
                             mode="clip")[:, 0]
    off = pos % page_size
    if active is not None:
        pg = jnp.where(active, pg, 0)
        off = jnp.where(active, off, 0)
    return pg, off


def _write_rows(cache, pg, off, rows, *, pages_distinct=False):
    """Write new rows into layer ``cache["layer"]`` of the stacked pools.

    ``rows`` maps pool keys to slot-layout rows ``(N, *row)``
    (``kvcache.pool_rows``); row ``n`` lands at page ``pg[n]``, offset
    ``off[n]`` (layouts in ``models/kvcache.paged_block_cache_shape``).
    Returns the written pool leaves.  K/V and latent rows are one window
    each.  A page of scales or rope keys holds a row's values in its lane
    rows, apart (scales: head ``h`` at lane ``h * page_size + off``) or
    together (rope keys: at ``off * rope_dim``).  Where no two rows share a
    page (``pages_distinct``: one decode row per lane, and idle lanes'
    garbage rows all on the trash page) each page's lane rows are read,
    patched and written back whole, one window a row: around a scatter of
    single values the compiler relays the whole pool out and back.
    """
    out, layer = {}, cache["layer"]
    for key, r in rows.items():
        pool = cache[key]
        if key in ("kp", "vp", "ckvp"):
            out[key] = pool.at[layer, pg, off].set(
                r.reshape(r.shape[0], -1).astype(pool.dtype))
            continue
        k, w = r.shape[-1], pool.shape[-1]
        if key == "krp":
            lanes = off[:, None] * k + jnp.arange(k)           # (N, k)
        else:
            lanes = off[:, None] + (w // k) * jnp.arange(k)
        if not pages_distinct:
            idx = ((layer, pg[:, None], lanes) if key == "krp" else
                   (layer, pg[:, None, None], jnp.arange(2)[:, None], lanes[:, None]))
            out[key] = pool.at[idx].set(r.astype(pool.dtype))
            continue
        lane = jnp.arange(w)
        if key == "krp":
            hit, full = lane // k == off[:, None], jnp.tile(r, (1, w // k))
        else:
            hit = (lane % (w // k) == off[:, None])[:, None]
            full = jnp.repeat(r, w // k, axis=-1)
        out[key] = pool.at[layer, pg].set(
            jnp.where(hit, full.astype(pool.dtype), pool[layer, pg]))
    return out


def _gather_pages(pool, key, layer, tables, page_size):
    """Layer ``layer`` of stacked pool ``key`` through (B, P) block tables:
    the (B, P * page_size, w) logically-contiguous per-lane rows in one
    gather (the Pallas kernel instead streams pages straight from the
    pool)."""
    return from_pages(key, pool[layer, tables], page_size)


def _kv_rows(cache, tables, page_size, head_dim):
    """The gathered (B, S, H, D) K/V views and (B, S, H) K and V scales
    (None for a bf16 pool) of layer ``cache["layer"]``."""
    b, smax, layer = tables.shape[0], tables.shape[1] * page_size, cache["layer"]
    k_all, v_all = (_gather_pages(cache[key], key, layer, tables, page_size)
                    .reshape(b, smax, -1, head_dim) for key in ("kp", "vp"))
    if "kvp_scale" not in cache:
        return k_all, v_all, None, None
    scales = _gather_pages(cache["kvp_scale"], "kvp_scale", layer, tables,
                           page_size)                      # (B, S, 2, H)
    return k_all, v_all, scales[:, :, 0], scales[:, :, 1]


def _kv_new_rows(k, v, int8_cache):
    """Slot-layout rows (..., H, D) of fresh K/V as pool rows, quantized
    into the int8 payload + scale rows for a byte-size pool."""
    if not int8_cache:
        return {"kp": k, "vp": v}
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return {"kp": kq, "vp": vq, "kvp_scale": jnp.stack([ks, vs], axis=-2)}


def paged_attention_decode(x_t, p, cfg: ModelConfig, cache, pos, tables, *,
                           active=None):
    """One-token decode over one layer of the stacked page pools.

    cache: a layer's view of the pools (``transformer.on_layer``):
    {"kp","vp"[,"kvp_scale"]} with a leading layer axis (layouts in
    ``models/kvcache.paged_block_cache_shape``) and "layer", the layer's
    index into them; tables: (B, P) int32 block tables; pos: (B,).  The
    new token's K/V is scattered into page ``tables[b, pos // page_size]``
    of the layer, in place, and attention runs over the gathered logical
    view (jnp twin) or streams the layer's pages inside the Pallas kernel.
    With ``P * page_size == cache_len`` the jnp twin is bitwise identical
    to :func:`attention_decode` on a slot cache.
    """
    b = x_t.shape[0]
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q, k, v = _decode_qkv(x_t, p, cfg, pos)

    page_size = page_size_of(cache)
    pg, off = _write_page(tables, pos, page_size, active)
    rows = _kv_new_rows(k[:, 0], v[:, 0], "kvp_scale" in cache)
    new_cache = dict(cache, **_write_rows(cache, pg, off, rows,
                                          pages_distinct=True))

    g = hq // hkv
    impl = _resolve_paged_impl(cfg)
    if impl == "jnp":
        qg = q.reshape(b, 1, g, hkv, hd)
        smax = tables.shape[1] * page_size
        valid = jnp.arange(smax)[None, :] <= pos[:, None]
        out = _decode_attend(qg, *_kv_rows(new_cache, tables, page_size, hd),
                             valid)
    else:
        from repro.shard.kernels import sharded_paged_attention

        qk = q[:, 0].reshape(b, g, hkv, hd).transpose(0, 2, 1, 3)  # (B,Hkv,G,D)
        out = sharded_paged_attention(
            qk, new_cache["kp"], new_cache["vp"], tables, pos + 1,
            cache["layer"],
            scales=new_cache.get("kvp_scale"),
            interpret=(impl == "pallas_interpret"),
        )
        out = out.transpose(0, 2, 1, 3)[:, None]  # (B, 1, G, Hkv, D)
    out = out.astype(x_t.dtype).reshape(b, 1, hq * hd)
    return linear(out, p["wo"], qm, be, tp="row"), new_cache


def _write_pages(tables, pos, w, page_size, active):
    """Multi-row variant of :func:`_write_page`: (B, W) page/offset pairs
    for the verify-window rows ``pos + [0, w)``.  Inactive lanes redirect
    to the reserved trash page 0 for the same pool-safety reason."""
    idx = pos[:, None] + jnp.arange(w)[None, :]                # (B, W)
    pg = jnp.take_along_axis(tables, idx // page_size, axis=1, mode="clip")
    off = idx % page_size
    if active is not None:
        pg = jnp.where(active[:, None], pg, 0)
        off = jnp.where(active[:, None], off, 0)
    return pg, off


def _flat_rows(rows):
    """(B, W, *row) rows as (B * W, *row)."""
    return {k: r.reshape((-1,) + r.shape[2:]) for k, r in rows.items()}


def paged_attention_verify(x, p, cfg: ModelConfig, cache, pos, tables, *,
                           active=None):
    """W-token speculative verify over one layer of the page pools (cache:
    a layer's view, as :func:`paged_attention_decode` takes).

    The paged twin of :func:`attention_verify`: scatters the window's W
    K/V rows through the block table (engine capacity checks reserve the
    overshoot pages up front) and attends the gathered logical view under
    the per-row causal mask.  Always the jnp gather twin — the Pallas
    decode kernel is single-query, and the bitwise greedy contract is
    anchored to the gather path.
    """
    b, w, _ = x.shape
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q, k, v = _verify_qkv(x, p, cfg, pos)

    page_size = page_size_of(cache)
    pg, off = _write_pages(tables, pos, w, page_size, active)
    rows = _kv_new_rows(k, v, "kvp_scale" in cache)
    new_cache = dict(cache, **_write_rows(cache, pg.reshape(-1), off.reshape(-1),
                                          _flat_rows(rows)))

    qg = q.reshape(b, w, hq // hkv, hkv, hd)
    valid = _verify_valid(pos, w, tables.shape[1] * page_size)
    out = _decode_attend(qg, *_kv_rows(new_cache, tables, page_size, hd), valid)
    out = out.astype(x.dtype).reshape(b, w, hq * hd)
    return linear(out, p["wo"], qm, be, tp="row"), new_cache


def _chunk_pages(tables_row, start, chunk, page_size):
    """Page/offset pairs for chunk positions ``start + [0, chunk)`` of one
    lane. tables_row: (1, P); start: (1,) int32. Returns ((C,), (C,))."""
    idx = start[:, None] + jnp.arange(chunk)[None, :]          # (1, C)
    pg = jnp.take_along_axis(tables_row, idx // page_size, axis=1, mode="clip")
    return pg[0], (idx % page_size)[0]


def attention_chunk(x, p, cfg: ModelConfig, cache, tables_row, start, *,
                    positions):
    """Chunked-prefill extend of one lane's paged KV (B == 1).

    x: (1, C, d) chunk hidden states; cache: this layer's view of the
    page pools (as :func:`paged_attention_decode` takes); tables_row:
    (1, P) block-table row; start: (1,) absolute position of the chunk's
    first token; positions: (1, C) for RoPE.  Writes the chunk's K/V into the lane's
    pages, then attends gathered prefix + chunk under the standard causal
    mask.  Prior chunks' rows are bitwise what full prefill computes (the
    bf16 cache roundtrip is lossless) and a padded tail is overwritten by
    the next chunk before any query can attend it, so chunking stays
    output-invisible.
    """
    b, cs, _ = x.shape
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    int8_cache = "kvp_scale" in cache
    q = linear(x, p["wq"], qm, be, tp="col").reshape(b, cs, hq, hd)
    k = linear(x, p["wk"], qm, be, tp="col").reshape(b, cs, hkv, hd)
    v = linear(x, p["wv"], qm, be, tp="col").reshape(b, cs, hkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    page_size = page_size_of(cache)
    pg, off = _chunk_pages(tables_row, start, cs, page_size)
    rows = _kv_new_rows(k[0], v[0], int8_cache)
    new_cache = dict(cache, **_write_rows(cache, pg, off, rows))

    k_all, v_all, ks_all, vs_all = _kv_rows(new_cache, tables_row, page_size, hd)
    if int8_cache:
        # prefill-side chunks attend the dequantized pages (tolerance path;
        # the exactness argument applies to the full-precision pools)
        k_all = (k_all.astype(jnp.float32) * ks_all[..., None]).astype(x.dtype)
        v_all = (v_all.astype(jnp.float32) * vs_all[..., None]).astype(x.dtype)
    qg = q.reshape(b, cs, hq // hkv, hkv, hd)
    out = _attend_chunk(qg, k_all, v_all, start[0], 0, True, None)
    out = out.astype(x.dtype).reshape(b, cs, hq * hd)
    return linear(out, p["wo"], qm, be, tp="row"), new_cache


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ModelConfig):
    return init_attention(key, cfg)


def cross_attention_block(x, enc_kv, p, cfg: ModelConfig):
    """x: (B, St, d) decoder states; enc_kv: precomputed (k, v) from encoder."""
    b, s, _ = x.shape
    hd, hq = cfg.resolved_head_dim, cfg.n_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q = linear(x, p["wq"], qm, be, tp="col").reshape(b, s, hq, hd)
    k, v = enc_kv
    out = multihead_attention(q, k, v, causal=False, window=None)
    return linear(out.reshape(b, s, hq * hd), p["wo"], qm, be, tp="row")


def encode_cross_kv(enc_out, p, cfg: ModelConfig):
    b, s, _ = enc_out.shape
    hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    qm, be = cfg.quant_mode, cfg.gemm_backend
    k = linear(enc_out, p["wk"], qm, be, tp="col").reshape(b, s, hkv, hd)
    v = linear(enc_out, p["wv"], qm, be, tp="col").reshape(b, s, hkv, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------

# HLO scope of decode / verify latent attention (the latent row write, the
# page gather, the absorbed einsums and the softmax), named for the device
# trace
MLA_SCOPE = "mla_attn"


def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    if m.q_lora_rank is None:   # DeepSeek-V2-Lite: a direct query projection
        q = {"w_q": init_linear(ks[0], d, h * qk_dim)}
    else:
        q = {"w_dq": init_linear(ks[0], d, m.q_lora_rank),
             "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
             "w_uq": init_linear(ks[1], m.q_lora_rank, h * qk_dim)}
    return {
        **q,
        "w_dkv": init_linear(ks[2], d, m.kv_lora_rank),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "w_uk": init_linear(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim),
        "w_uv": init_linear(ks[4], m.kv_lora_rank, h * m.v_head_dim),
        "w_kr": init_linear(ks[5], d, m.qk_rope_head_dim),
        "wo": init_linear(ks[6], h * m.v_head_dim, d),
    }


def _mla_qkv(x, p, cfg, positions):
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    qm, be = cfg.quant_mode, cfg.gemm_backend
    if m.q_lora_rank is None:
        q = linear(x, p["w_q"], qm, be, tp="col")
    else:
        cq = rmsnorm(linear(x, p["w_dq"], qm, be), p["q_norm"], cfg.norm_eps)
        q = linear(cq, p["w_uq"], qm, be, tp="col")
    q_nope, q_rope = jnp.split(q.reshape(b, s, h, -1), [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    c_kv = rmsnorm(linear(x, p["w_dkv"], qm, be), p["kv_norm"], cfg.norm_eps)
    k_rope = linear(x, p["w_kr"], qm, be).reshape(b, s, 1, m.qk_rope_head_dim)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope, c_kv, k_rope


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``qk_dim ** -0.5``, times YaRN's ``mscale(factor, mscale_all_dim)``
    squared where the configuration extends its context (DeepSeek-V2)."""
    m, rs = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def mla_block(x, p, cfg: ModelConfig, positions):
    """Training / prefill MLA (non-absorbed: reconstruct K, V per token)."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(x, p, cfg, positions)
    k_nope = linear(c_kv, p["w_uk"], qm, be, tp="col").reshape(b, s, h, m.qk_nope_head_dim)
    v = linear(c_kv, p["w_uv"], qm, be, tp="col").reshape(b, s, h, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_head_dim))], axis=-1)
    out = multihead_attention(q, k, v, causal=True, scale=mla_softmax_scale(cfg))
    out = linear(out.reshape(b, s, h * m.v_head_dim), p["wo"], qm, be, tp="row")
    return out, (c_kv, k_rope.reshape(b, s, m.qk_rope_head_dim))


def _mla_attend(q_nope, q_rope, ckv_view, kr_view, pos, p, cfg: ModelConfig):
    """Absorbed-matmul MLA attention over a logically-contiguous latent view.

    ckv_view: (B, S, kv_lora_rank); kr_view: (B, S, rope_dim).  Shared by
    the slot path and the paged gather twin (same structural-identity
    argument as ``_decode_attend``).  q_nope/q_rope may carry C > 1 query
    rows (the speculative verify window); row c then attends positions
    <= pos + c. Returns (B, C, H * v_head_dim).
    """
    m, h = cfg.mla, cfg.n_heads
    b = q_nope.shape[0]
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum(
        "bchd,lhd->bchl", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32)
    )  # (B,1,H,latent)
    scores = jnp.einsum("bchl,bsl->bchs", q_lat.astype(ckv_view.dtype), ckv_view,
                        preferred_element_type=jnp.float32)
    scores += jnp.einsum("bchr,bsr->bchs", q_rope.astype(kr_view.dtype), kr_view,
                         preferred_element_type=jnp.float32)
    scores *= mla_softmax_scale(cfg)
    smax = ckv_view.shape[1]
    c = q_nope.shape[1]
    if c == 1:
        valid = jnp.arange(smax)[None, :] <= pos[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    else:
        row_pos = pos[:, None] + jnp.arange(c)[None, :]
        valid = jnp.arange(smax)[None, None, :] <= row_pos[:, :, None]
        scores = jnp.where(valid[:, :, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bchs,bsl->bchl", probs.astype(ckv_view.dtype), ckv_view,
                         preferred_element_type=jnp.float32)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bchl,lhv->bchv", out_lat, w_uv.astype(jnp.float32))
    return out.reshape(b, c, h * m.v_head_dim)


def mla_decode(x_t, p, cfg: ModelConfig, ckv_cache, krope_cache, pos):
    """Absorbed-matmul MLA decode: attention runs in the latent space, the
    cache holds only (c_kv, k_rope) — the MLA memory saving."""
    m = cfg.mla
    b = x_t.shape[0]
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q_nope, q_rope, c_kv_t, k_rope_t = _mla_qkv(x_t, p, cfg, pos[:, None])

    with jax.named_scope(MLA_SCOPE):
        ckv_cache = jax.vmap(
            lambda c, t, i: jax.lax.dynamic_update_slice_in_dim(c, t, i, axis=0)
        )(ckv_cache, c_kv_t, pos)
        krope_cache = jax.vmap(
            lambda c, t, i: jax.lax.dynamic_update_slice_in_dim(c, t, i, axis=0)
        )(krope_cache, k_rope_t.reshape(b, 1, m.qk_rope_head_dim), pos)
        out = _mla_attend(q_nope, q_rope, ckv_cache, krope_cache, pos, p, cfg)
    out = out.astype(x_t.dtype)
    return linear(out, p["wo"], qm, be, tp="row"), (ckv_cache, krope_cache)


def mla_verify(x, p, cfg: ModelConfig, ckv_cache, krope_cache, pos):
    """W-token speculative verify over the slot latent caches (the MLA
    twin of :func:`attention_verify`; same garbage-row-overwrite and
    row-for-row bitwise arguments)."""
    m = cfg.mla
    b, w, _ = x.shape
    qm, be = cfg.quant_mode, cfg.gemm_backend
    positions = pos[:, None] + jnp.arange(w)[None, :]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(x, p, cfg, positions)

    with jax.named_scope(MLA_SCOPE):
        ckv_cache = jax.vmap(
            lambda c, t, i: jax.lax.dynamic_update_slice_in_dim(c, t, i, axis=0)
        )(ckv_cache, c_kv, pos)
        krope_cache = jax.vmap(
            lambda c, t, i: jax.lax.dynamic_update_slice_in_dim(c, t, i, axis=0)
        )(krope_cache, k_rope.reshape(b, w, m.qk_rope_head_dim), pos)
        out = _mla_attend(q_nope, q_rope, ckv_cache, krope_cache, pos, p, cfg)
    out = out.astype(x.dtype)
    return linear(out, p["wo"], qm, be, tp="row"), (ckv_cache, krope_cache)


def _mla_new_rows(c_kv, k_rope):
    """(N, rank) latents and (N, 1, rope) rope keys as pool rows."""
    return {"ckvp": c_kv, "krp": k_rope.reshape(c_kv.shape[0], -1)}


def _mla_views(cache, tables):
    """Layer ``cache["layer"]``'s latent and rope-key rows of every lane's
    table."""
    page_size, layer = page_size_of(cache), cache["layer"]
    return (_gather_pages(cache["ckvp"], "ckvp", layer, tables, page_size),
            _gather_pages(cache["krp"], "krp", layer, tables, page_size))


def mla_paged_decode(x_t, p, cfg: ModelConfig, cache, pos, tables, *,
                     active=None):
    """Absorbed MLA decode over one layer of the latent page pools.

    cache: a layer's view of the pools (``transformer.on_layer``):
    {"ckvp","krp"} with a leading layer axis (layouts in
    ``models/kvcache.paged_block_cache_shape``) and "layer", the layer's
    index into them.  The compressed latents are already the MLA memory
    saving; paging makes the *pool* shared across lanes.  jnp gather twin
    only: one gather of the layer's pages of every lane's whole table.
    """
    q_nope, q_rope, c_kv_t, k_rope_t = _mla_qkv(x_t, p, cfg, pos[:, None])

    with jax.named_scope(MLA_SCOPE):
        pg, off = _write_page(tables, pos, page_size_of(cache), active)
        rows = _mla_new_rows(c_kv_t[:, 0], k_rope_t[:, 0])
        new_cache = dict(cache, **_write_rows(cache, pg, off, rows,
                                              pages_distinct=True))
        ckv_view, kr_view = _mla_views(new_cache, tables)
        out = _mla_attend(q_nope, q_rope, ckv_view, kr_view, pos, p, cfg)
    out = out.astype(x_t.dtype)
    return linear(out, p["wo"], cfg.quant_mode, cfg.gemm_backend,
                  tp="row"), new_cache


def mla_paged_verify(x, p, cfg: ModelConfig, cache, pos, tables, *,
                     active=None):
    """W-token speculative verify over the latent page pools (the paged
    twin of :func:`mla_verify`; gather path only, like
    :func:`mla_paged_decode`)."""
    b, w, _ = x.shape
    positions = pos[:, None] + jnp.arange(w)[None, :]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(x, p, cfg, positions)

    with jax.named_scope(MLA_SCOPE):
        pg, off = _write_pages(tables, pos, w, page_size_of(cache), active)
        rows = _mla_new_rows(c_kv.reshape(b * w, -1), k_rope)
        new_cache = dict(cache, **_write_rows(cache, pg.reshape(-1),
                                              off.reshape(-1), rows))
        ckv_view, kr_view = _mla_views(new_cache, tables)
        out = _mla_attend(q_nope, q_rope, ckv_view, kr_view, pos, p, cfg)
    out = out.astype(x.dtype)
    return linear(out, p["wo"], cfg.quant_mode, cfg.gemm_backend,
                  tp="row"), new_cache


def mla_chunk(x, p, cfg: ModelConfig, cache, tables_row, start, *, positions):
    """Chunked-prefill extend of one lane's paged MLA latents (B == 1).

    Mirrors :func:`mla_block` (the non-absorbed prefill form): the chunk's
    latents are written to pages, then K/V are *recomputed from the
    gathered latents* via the up-projections — bitwise the values the full
    prefill computes, because the latent cache roundtrips bf16 losslessly
    and the up-projection is row-independent.  This keeps chunked MLA
    admission output-invisible even though decode later switches to the
    absorbed form (exactly like the unchunked prefill -> decode handoff).
    """
    m, h = cfg.mla, cfg.n_heads
    b, cs, _ = x.shape
    qm, be = cfg.quant_mode, cfg.gemm_backend
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(x, p, cfg, positions)

    pg, off = _chunk_pages(tables_row, start, cs, page_size_of(cache))
    rows = _mla_new_rows(c_kv[0], k_rope[0])
    new_cache = dict(cache, **_write_rows(cache, pg, off, rows))

    ckv_all, kr_all = _mla_views(new_cache, tables_row)        # (1, L, rank/rope)
    smax = ckv_all.shape[1]
    k_nope = linear(ckv_all, p["w_uk"], qm, be, tp="col").reshape(b, smax, h, m.qk_nope_head_dim)
    v_all = linear(ckv_all, p["w_uv"], qm, be, tp="col").reshape(b, smax, h, m.v_head_dim)
    k_all = jnp.concatenate(
        [k_nope,
         jnp.broadcast_to(kr_all[:, :, None, :], (b, smax, h, m.qk_rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    qg = q.reshape(b, cs, 1, h, q.shape[-1])
    out = _attend_chunk(qg, k_all, v_all, start[0], 0, True, None,
                        mla_softmax_scale(cfg))
    out = out.astype(x.dtype).reshape(b, cs, h * m.v_head_dim)
    return linear(out, p["wo"], qm, be, tp="row"), new_cache
