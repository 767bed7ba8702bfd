"""Public model API: init / forward / loss / prefill / decode for every
assigned architecture (decoder-only, enc-dec, hybrid, frontend-stub).

Parameter layout (scan-friendly):

    {"embed": (V, d),
     "head_blocks": [per-layer trees]            # leading dense layers (MoE)
     "blocks": (slot_0_tree, ..., slot_{p-1}),   # stacked over n_periods
     "tail_blocks": [per-layer trees],           # depth remainder
     "final_norm": (d,),
     "head": (V, d) (absent if tied),
     # enc-dec only:
     "enc_embed_norm", "enc_blocks", "enc_final_norm", "dec_*" mirrors}

The cross-entropy is computed CHUNKED over the sequence (scan) so the full
(B, S, V) logits tensor is never materialized — with 256k vocabs at 1M
tokens that buffer alone would exceed per-device HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import transformer as tfm
from repro.models.kvcache import (
    block_cache_shape,
    paged_block_cache_shape,
    zeros_like_shapes,
)
from repro.models.layers import (
    COMPUTE_DTYPE,
    embed,
    init_embedding,
    init_rmsnorm,
    rmsnorm,
    unembed,
)
from repro.obs import watchdog as _watchdog

LOSS_CHUNK = 512


def _watched(tag: str):
    """Wrap a ``(params, cfg, ...)`` entry point in the numerics-watchdog
    trace-time context when ``cfg.numerics_watchdog`` asks for it.

    The context is consulted by ``quantized_linear`` *while JAX traces
    the body*, so every quantized GEMM below self-labels
    (``<tag>.<site>.k<K>n<N>``) without threading a flag through the
    model call tree.  ``cfg.numerics_watchdog`` is part of every jit
    cache key, so toggling can never reuse an uninstrumented trace.
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(params, cfg, *args, **kw):
            with _watchdog.watching(tag if cfg.numerics_watchdog else None):
                return fn(params, cfg, *args, **kw)
        return wrapper
    return deco


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _stack_period_params(keys_2d, cfg: ModelConfig):
    """init each slot across periods, stacked along a leading axis.

    One vmapped init per slot (the same values as stacking per-period
    inits): a jitted init then compiles one layer's program, not one per
    layer, which at 40 layers is most of its compile time."""
    slots = []
    for s, kind in enumerate(cfg.block_pattern):
        keys = jnp.stack([row[s] for row in keys_2d])
        slots.append(jax.vmap(lambda k, kind=kind: tfm.init_block(k, kind, cfg))(keys))
    return tuple(slots)


def _init_stack(key, cfg: ModelConfig, n_layers: int):
    lead, n_periods, tail_kinds = tfm.layer_layout(cfg, n_layers)
    keys = jax.random.split(key, lead + n_periods * cfg.pattern_period + len(tail_kinds) + 1)
    out = {}
    ki = 0
    if lead:
        out["head_blocks"] = []
        for i in range(lead):
            out["head_blocks"].append(tfm.init_block(keys[ki], "dense_ffn_layer", cfg))
            ki += 1
    keys_2d = []
    for i in range(n_periods):
        keys_2d.append([keys[ki + j] for j in range(cfg.pattern_period)])
        ki += cfg.pattern_period
    out["blocks"] = _stack_period_params(keys_2d, cfg) if n_periods else ()
    out["tail_blocks"] = []
    for kind in tail_kinds:
        out["tail_blocks"].append(tfm.init_block(keys[ki], kind, cfg))
        ki += 1
    return out


def init_params(cfg: ModelConfig, key):
    k_embed, k_stack, k_head, k_enc = jax.random.split(key, 4)
    params = {"embed": init_embedding(k_embed, cfg.vocab_size, cfg.d_model)}
    params.update(_init_stack(k_stack, cfg, cfg.n_layers))
    params["final_norm"] = init_rmsnorm(cfg.d_model)
    if not cfg.tie_embeddings:
        params["head"] = init_embedding(k_head, cfg.vocab_size, cfg.d_model)
    if cfg.is_encoder_decoder:
        enc_cfg = cfg.with_(block_pattern=("attn",))
        enc = _init_stack(k_enc, enc_cfg, cfg.n_encoder_layers)
        params["enc_blocks"] = enc["blocks"]
        params["enc_tail_blocks"] = enc["tail_blocks"]
        params["enc_final_norm"] = init_rmsnorm(cfg.d_model)
        # cross-attention params per decoder layer (stacked like blocks)
        kx = jax.random.split(k_enc, max(cfg.n_layers, 1))
        lead, n_periods, tail_kinds = tfm.layer_layout(cfg)
        per = []
        for i in range(n_periods):
            per.append(
                {
                    "xattn": attn_mod.init_cross_attention(kx[i], cfg),
                    "norm_x": init_rmsnorm(cfg.d_model),
                }
            )
        params["cross_blocks"] = (
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per) if per else ()
        )
    return params


def param_shapes(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# Backbone forward (training / full-sequence)
# ---------------------------------------------------------------------------

def _embed_input(params, cfg: ModelConfig, batch):
    if "embeds" in batch:
        x = batch["embeds"].astype(COMPUTE_DTYPE)
    else:
        x = embed(batch["tokens"], params["embed"])
    b, s = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions


def _run_stack(x, params, cfg: ModelConfig, positions, *, causal=True):
    aux = jnp.zeros((), jnp.float32)
    for p in params.get("head_blocks", []):
        x, a, _ = tfm.apply_block(x, p, "dense_ffn_layer", cfg, positions, causal=causal)
        aux += a
    if params.get("blocks", ()):
        x, a = tfm.scan_periods(x, params["blocks"], cfg, positions, causal=causal)
        aux += a
    tail_kinds = tfm.layer_layout(cfg)[2] if params.get("tail_blocks") else ()
    for i, p in enumerate(params.get("tail_blocks", [])):
        x, a, _ = tfm.apply_block(x, p, tail_kinds[i], cfg, positions, causal=causal)
        aux += a
    return x, aux


def _run_encoder(src, params, cfg: ModelConfig):
    enc_cfg = cfg.with_(block_pattern=("attn",))
    b, s = src.shape[0], src.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = src.astype(COMPUTE_DTYPE)
    enc_params = {"blocks": params["enc_blocks"], "tail_blocks": params.get("enc_tail_blocks", [])}
    x, _ = _run_stack(x, enc_params, enc_cfg, positions)
    return rmsnorm(x, params["enc_final_norm"], cfg.norm_eps)


def _run_decoder_with_cross(x, params, cfg: ModelConfig, positions, enc_out):
    """Decoder stack with interleaved cross-attention (enc-dec models)."""
    pattern = cfg.block_pattern
    aux = jnp.zeros((), jnp.float32)

    def period_fn(carry, xs):
        from repro.runtime.sharding import constrain_activations

        h, aux = carry
        h = constrain_activations(h)
        slot_params, cross_p = xs
        for s, kind in enumerate(pattern):
            h, a, _ = tfm.apply_block(h, slot_params[s], kind, cfg, positions)
            aux = aux + a
        hx = rmsnorm(h, cross_p["norm_x"], cfg.norm_eps)
        enc_kv = attn_mod.encode_cross_kv(enc_out, cross_p["xattn"], cfg)
        h = h + attn_mod.cross_attention_block(hx, enc_kv, cross_p["xattn"], cfg)
        return (h, aux), None

    if cfg.remat:
        period_fn = jax.checkpoint(period_fn, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(
        period_fn, (x, aux), (params["blocks"], params["cross_blocks"]),
        unroll=cfg.scan_unroll,
    )
    return x, aux


def backbone(params, cfg: ModelConfig, batch):
    """-> (hidden (B,S,d), aux_loss scalar)."""
    if cfg.is_encoder_decoder:
        enc_out = _run_encoder(batch["src_embeds"], params, cfg)
        x, positions = _embed_input(params, cfg, {"tokens": batch["tgt_tokens"]})
        x, aux = _run_decoder_with_cross(x, params, cfg, positions, enc_out)
    else:
        x, positions = _embed_input(params, cfg, batch)
        x, aux = _run_stack(x, params, cfg, positions)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps), aux


def _head_table(params, cfg: ModelConfig):
    return params["embed"] if cfg.tie_embeddings else params["head"]


@_watched("forward")
def forward(params, cfg: ModelConfig, batch):
    """Full logits (B, S, V) — use only for small configs/tests."""
    h, _ = backbone(params, cfg, batch)
    return unembed(h, _head_table(params, cfg))


def _chunked_ce(hidden, labels, mask, table, cfg: ModelConfig):
    """Cross-entropy via scan over sequence chunks; no (B,S,V) buffer."""
    b, s, d = hidden.shape
    c = LOSS_CHUNK if s % LOSS_CHUNK == 0 and s > LOSS_CHUNK else s
    nc = s // c
    hs = hidden.reshape(b, nc, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, nc, c).transpose(1, 0, 2)
    ms = mask.reshape(b, nc, c).transpose(1, 0, 2)

    # remat: the scan's backward would otherwise save every chunk's logits —
    # the very (B, S, V) buffer this chunking exists to avoid.
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(acc, xs):
        hc, lc, mc = xs
        logits = unembed(hc, table).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mc
        return (acc[0] + nll.sum(), acc[1] + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (hs, ls, ms),
                                 unroll=cfg.scan_unroll)
    return tot / jnp.maximum(cnt, 1.0)


AUX_LOSS_WEIGHT = 0.01


@_watched("loss")
def lm_loss(params, cfg: ModelConfig, batch):
    """Next-token (or label) cross-entropy + MoE aux loss. Scalar fp32."""
    h, aux = backbone(params, cfg, batch)
    if cfg.is_encoder_decoder:
        tokens = batch["tgt_tokens"]
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        mask = jnp.pad(jnp.ones_like(tokens[:, 1:], jnp.float32), ((0, 0), (0, 1)))
    elif "labels" in batch:
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
    else:
        tokens = batch["tokens"]
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        mask = jnp.pad(jnp.ones_like(tokens[:, 1:], jnp.float32), ((0, 0), (0, 1)))
    ce = _chunked_ce(h, labels, mask, _head_table(params, cfg), cfg)
    return ce + AUX_LOSS_WEIGHT * aux


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def cache_shapes(cfg: ModelConfig, batch: int, cache_len: int, cross_len: int = 0):
    """ShapeDtypeStruct cache pytree mirroring the block layout."""
    lead, n_periods, tail_kinds = tfm.layer_layout(cfg)

    def one(kind):
        return block_cache_shape(tfm.effective_kind(kind, cfg), cfg, batch, cache_len)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n_periods,) + s.shape, s.dtype), tree
        )

    cache = {
        "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
        "head_blocks": [one("attn") for _ in range(lead)],
        "blocks": tuple(stack(one(kind)) for kind in cfg.block_pattern) if n_periods else (),
        "tail_blocks": [one(kind) for kind in tail_kinds],
    }
    if cfg.is_encoder_decoder:
        hd = cfg.resolved_head_dim
        kv = jax.ShapeDtypeStruct(
            (n_periods, batch, cross_len, cfg.n_kv_heads, hd), COMPUTE_DTYPE
        )
        cache["cross_kv"] = {"k": kv, "v": kv}
    return cache


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, cross_len: int = 0):
    return zeros_like_shapes(cache_shapes(cfg, batch, cache_len, cross_len))


def paged_cache_shapes(cfg: ModelConfig, n_lanes: int, cache_len: int,
                       page_size: int, n_pages: int):
    """ShapeDtypeStruct tree for the *paged* decode cache (repro/paging/).

    Same block layout as :func:`cache_shapes`, but attention-family KV
    lives in global page pools indexed through ``block_tables`` —
    ``(n_lanes, max_pages_per_lane)`` int32, logical page ``j`` of lane
    ``b`` is physical page ``block_tables[b, j]``.  ``cache_len`` bounds a
    single lane (it sizes the table width), not the pool.
    """
    if cfg.is_encoder_decoder:
        raise ValueError("paged caches support decoder-only stacks")
    from repro.configs.base import pages_for

    lead, n_periods, tail_kinds = tfm.layer_layout(cfg)
    max_pages = pages_for(cache_len, page_size)

    def one(kind):
        return paged_block_cache_shape(
            tfm.effective_kind(kind, cfg), cfg, n_lanes, cache_len,
            n_pages, page_size)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n_periods,) + s.shape, s.dtype), tree
        )

    return {
        "pos": jax.ShapeDtypeStruct((n_lanes,), jnp.int32),
        "block_tables": jax.ShapeDtypeStruct((n_lanes, max_pages), jnp.int32),
        "head_blocks": [one("dense_ffn_layer") for _ in range(lead)],
        "blocks": tuple(stack(one(kind)) for kind in cfg.block_pattern) if n_periods else (),
        "tail_blocks": [one(kind) for kind in tail_kinds],
    }


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

@_watched("prefill")
def prefill(params, cfg: ModelConfig, batch, cache_len: int, lengths=None):
    """Process the prompt, return (last-position logits (B, V), cache).

    ``lengths`` (optional, (B,) int32): true prompt lengths when the batch is
    right-padded to a common width (the serving engine's prefill buckets).
    Logits are gathered at position ``lengths - 1`` and the cache ``pos``
    starts at ``lengths``, so padded tail positions are never attended: every
    decode step writes its K/V at ``pos`` *before* attending ``kpos <= pos``,
    overwriting the stale padded row exactly when it would first become
    visible.  Exact for attention-family caches only — recurrent state
    (rglru/mlstm/slstm) integrates padded tokens, so callers must pass
    unpadded prompts (``lengths=None``) for those stacks.
    """
    for key in ("tokens", "embeds", "src_embeds"):
        if key in batch:
            b = batch[key].shape[0]
            break
    if cfg.is_encoder_decoder:
        enc_out = _run_encoder(batch["src_embeds"], params, cfg)
        cross_len = enc_out.shape[1]
        cache = init_cache(cfg, b, cache_len, cross_len)
        # precompute per-decoder-layer cross K/V once (the enc-dec prefill)
        def xkv(cross_p):
            return attn_mod.encode_cross_kv(enc_out, cross_p["xattn"], cfg)
        k, v = jax.vmap(xkv)(params["cross_blocks"])
        cache["cross_kv"] = {"k": k, "v": v}
        tgt = batch.get("tgt_tokens")
        x, positions = _embed_input(params, cfg, {"tokens": tgt})
    else:
        cache = init_cache(cfg, b, cache_len)
        x, positions = _embed_input(params, cfg, batch)
    s = x.shape[1]

    aux = jnp.zeros((), jnp.float32)
    for i, p in enumerate(params.get("head_blocks", [])):
        x, a, c = tfm.apply_block_prefill(x, p, "dense_ffn_layer", cfg, positions,
                                          cache["head_blocks"][i])
        cache["head_blocks"][i] = c
    if params.get("blocks", ()):
        if cfg.is_encoder_decoder:
            x, aux2, new_blocks = _prefill_decoder_with_cross(
                x, params, cfg, positions, cache
            )
        else:
            x, aux2, new_blocks = tfm.scan_periods_prefill(
                x, params["blocks"], cache["blocks"], cfg, positions
            )
        cache["blocks"] = new_blocks
    lead, n_periods, tail_kinds = tfm.layer_layout(cfg)
    for i, p in enumerate(params.get("tail_blocks", [])):
        x, a, c = tfm.apply_block_prefill(x, p, tail_kinds[i], cfg, positions,
                                          cache["tail_blocks"][i])
        cache["tail_blocks"][i] = c
    if lengths is None:
        cache["pos"] = jnp.full((x.shape[0],), s, jnp.int32)
        x_last = x[:, -1:, :]
    else:
        lengths = lengths.astype(jnp.int32)
        cache["pos"] = lengths
        idx = jnp.clip(lengths - 1, 0, s - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    h = rmsnorm(x_last, params["final_norm"], cfg.norm_eps)
    logits = unembed(h, _head_table(params, cfg))[:, 0, :]
    return logits, cache


def _prefill_decoder_with_cross(x, params, cfg, positions, cache):
    pattern = cfg.block_pattern

    def period_fn(carry, xs):
        h, aux = carry
        slot_params, cross_p, slot_tpl, xkv = xs
        new_cache = []
        for s, kind in enumerate(pattern):
            h, a, c = tfm.apply_block_prefill(h, slot_params[s], kind, cfg, positions,
                                              slot_tpl[s])
            aux = aux + a
            new_cache.append(c)
        hx = rmsnorm(h, cross_p["norm_x"], cfg.norm_eps)
        h = h + attn_mod.cross_attention_block(hx, (xkv["k"], xkv["v"]), cross_p["xattn"], cfg)
        return (h, aux), tuple(new_cache)

    (x, aux), new_blocks = jax.lax.scan(
        period_fn,
        (x, jnp.zeros((), jnp.float32)),
        (params["blocks"], params["cross_blocks"], cache["blocks"], cache["cross_kv"]),
        unroll=cfg.scan_unroll,
    )
    return x, aux, new_blocks


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

@_watched("decode")
def decode_step(params, cfg: ModelConfig, tokens, cache, active=None):
    """One token for every sequence. tokens: (B,) int32 (or (B,d) embeds).

    ``active`` (optional, (B,) bool): lanes currently serving a request.
    Inactive lanes still ride the fixed-shape step (continuous batching),
    but their ``pos`` is pinned to 0 instead of advancing on garbage
    tokens — so host metrics and paged-page accounting can never observe
    a drifted position — and paged writes are redirected to the reserved
    trash page.  ``active=None`` (solo decoding) advances every lane.

    A ``block_tables`` key in ``cache`` marks a *paged* cache (see
    :func:`paged_cache_shapes`); the table is threaded to every
    attention-family block and passed through unchanged.

    Returns (logits (B, V), new cache with pos advanced)."""
    pos = cache["pos"]
    tables = cache.get("block_tables")
    if tokens.ndim == 1:
        x = embed(tokens[:, None], params["embed"])
    else:
        x = tokens[:, None, :].astype(COMPUTE_DTYPE)

    new_cache = dict(cache)
    for i, p in enumerate(params.get("head_blocks", [])):
        x, c = tfm.apply_block_decode(x, p, "dense_ffn_layer", cfg, cache["head_blocks"][i], pos,
                                      tables=tables, active=active)
        new_cache["head_blocks"] = list(new_cache.get("head_blocks", []))
        new_cache["head_blocks"][i] = c
    if params.get("blocks", ()):
        if cfg.is_encoder_decoder:
            x, nb = _decode_with_cross(x, params, cfg, cache, pos)
        else:
            x, nb = tfm.scan_periods_decode(x, params["blocks"], cache["blocks"], cfg, pos,
                                            tables=tables, active=active)
        new_cache["blocks"] = nb
    lead, n_periods, tail_kinds = tfm.layer_layout(cfg)
    for i, p in enumerate(params.get("tail_blocks", [])):
        x, c = tfm.apply_block_decode(x, p, tail_kinds[i], cfg, cache["tail_blocks"][i], pos,
                                      tables=tables, active=active)
        new_cache["tail_blocks"] = list(new_cache.get("tail_blocks", []))
        new_cache["tail_blocks"][i] = c
    new_cache["pos"] = pos + 1 if active is None else jnp.where(active, pos + 1, 0)
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(h, _head_table(params, cfg))[:, 0, :]
    return logits, new_cache


@_watched("verify")
def verify_step(params, cfg: ModelConfig, tokens, cache, active=None):
    """W tokens for every sequence in one dispatch (speculative verify).

    tokens: (B, W) int32 — the last accepted token followed by W-1 drafted
    tokens; row c is processed at absolute position ``pos + c`` (causal
    within the window, attending the full slot/paged history before it).
    All W K/V rows are written; the caller is responsible for treating
    rows past the accepted prefix as garbage (they are overwritten before
    any later query attends them).

    Unlike :func:`decode_step`, ``cache["pos"]`` is returned UNCHANGED —
    the accept length is only known after comparing logits, so the
    speculative wrapper advances pos by ``accepted + 1`` itself.

    Only ``chunkable(cfg)`` stacks are supported (attn / MLA / dense FFN;
    no MoE, recurrent, local-attn, or encoder-decoder blocks).

    Returns (logits (B, W, V), new cache with pos unchanged)."""
    pos = cache["pos"]
    tables = cache.get("block_tables")
    x = embed(tokens, params["embed"])

    new_cache = dict(cache)
    for i, p in enumerate(params.get("head_blocks", [])):
        x, c = tfm.apply_block_verify(x, p, "dense_ffn_layer", cfg, cache["head_blocks"][i], pos,
                                      tables=tables, active=active)
        new_cache["head_blocks"] = list(new_cache.get("head_blocks", []))
        new_cache["head_blocks"][i] = c
    if params.get("blocks", ()):
        x, nb = tfm.scan_periods_verify(x, params["blocks"], cache["blocks"], cfg, pos,
                                        tables=tables, active=active)
        new_cache["blocks"] = nb
    lead, n_periods, tail_kinds = tfm.layer_layout(cfg)
    for i, p in enumerate(params.get("tail_blocks", [])):
        x, c = tfm.apply_block_verify(x, p, tail_kinds[i], cfg, cache["tail_blocks"][i], pos,
                                      tables=tables, active=active)
        new_cache["tail_blocks"] = list(new_cache.get("tail_blocks", []))
        new_cache["tail_blocks"][i] = c
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(h, _head_table(params, cfg))
    return logits, new_cache


def _decode_with_cross(x_t, params, cfg, cache, pos):
    pattern = cfg.block_pattern

    def period_fn(h, xs):
        slot_params, cross_p, slot_cache, xkv = xs
        new_cache = []
        for s, kind in enumerate(pattern):
            h, c = tfm.apply_block_decode(h, slot_params[s], kind, cfg, slot_cache[s], pos)
            new_cache.append(c)
        hx = rmsnorm(h, cross_p["norm_x"], cfg.norm_eps)
        h = h + attn_mod.cross_attention_block(hx, (xkv["k"], xkv["v"]), cross_p["xattn"], cfg)
        return h, tuple(new_cache)

    x_t, new_blocks = jax.lax.scan(
        period_fn, x_t,
        (params["blocks"], params["cross_blocks"], cache["blocks"], cache["cross_kv"]),
        unroll=cfg.scan_unroll,
    )
    return x_t, new_blocks
