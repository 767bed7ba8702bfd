"""Stack assembly: blocks, scan-over-periods, decoder-only / enc-dec stacks.

The layer pattern (cfg.block_pattern) is cycled through the depth.  Layers
are grouped into ``n_periods`` repetitions of the pattern; parameters of
slot *s* are stacked along a leading period axis so one ``lax.scan``
(optionally rematerialized) executes the whole stack with O(1) compile-time
in depth.  Remainder layers ("tail", e.g. RecurrentGemma's 38 = 12*3 + 2)
and leading dense-FFN layers (DeepSeekMoE's first layer) run unrolled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models.kvcache import POOL_KEYS, is_paged
from repro.models.layers import glu_mlp, init_glu_mlp, init_rmsnorm, rmsnorm


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------

def effective_kind(kind: str, cfg: ModelConfig) -> str:
    if kind == "attn" and cfg.use_mla:
        return "mla"
    return kind


ATTN_KINDS = ("attn", "local_attn", "mla", "moe", "dense_ffn_layer")


def init_block(key, kind: str, cfg: ModelConfig):
    kind = effective_kind(kind, cfg)
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    p = {"norm1": init_rmsnorm(d)}
    if kind in ATTN_KINDS:
        p["attn"] = (attn.init_mla(k1, cfg) if cfg.uses_mla(kind)
                     else attn.init_attention(k1, cfg))
        p["norm2"] = init_rmsnorm(d)
        if kind == "moe":
            p["moe"] = moe_mod.init_moe(k2, cfg)
        else:   # a MoE stack's leading dense layer(s) take their own width
            width = cfg.moe.d_ff_dense if kind == "dense_ffn_layer" else 0
            p["mlp"] = init_glu_mlp(k2, d, width or cfg.d_ff)
    elif kind == "rglru":
        p["cell"] = rec.init_rglru(k1, cfg)
        p["norm2"] = init_rmsnorm(d)
        p["mlp"] = init_glu_mlp(k2, d, cfg.d_ff)
    elif kind == "mlstm":
        p["cell"] = rec.init_mlstm(k1, cfg)
    elif kind == "slstm":
        p["cell"] = rec.init_slstm(k1, cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


def apply_block(x, p, kind: str, cfg: ModelConfig, positions, *, causal=True):
    """Full-sequence (train/prefill) application. Returns (x, aux, cache_out).

    Megatron-SP boundaries: activations live seq-sharded over "model"
    between layers; ``sp_enter`` all-gathers the sequence entering each
    TP region (attention / MLP) and ``sp_exit`` reduce-scatters the
    row-parallel output back — otherwise the SPMD partitioner prefers to
    all-gather the much larger TP weight shards (see runtime/sharding.py).
    """
    from repro.runtime.sharding import sp_enter, sp_exit

    kind = effective_kind(kind, cfg)
    aux = jnp.zeros((), jnp.float32)
    cache_out = None
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if kind in ATTN_KINDS:
        if cfg.uses_mla(kind):
            a, cache_out = attn.mla_block(sp_enter(h), p["attn"], cfg, positions)
        else:
            window = cfg.sliding_window if kind == "local_attn" else None
            a, cache_out = attn.attention_block(sp_enter(h), p["attn"], cfg, positions,
                                                causal=causal, window=window)
        x = x + sp_exit(a)
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if kind == "moe":
            f, aux = moe_mod.moe_ffn(h2, p["moe"], cfg)
        else:
            f = sp_exit(glu_mlp(sp_enter(h2), p["mlp"], cfg.act, cfg.quant_mode, backend=cfg.gemm_backend))
        x = x + f
    elif kind == "rglru":
        a, state = rec.rglru_block(h, p["cell"], cfg, None)
        x = x + a
        cache_out = state
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + glu_mlp(h2, p["mlp"], cfg.act, cfg.quant_mode, backend=cfg.gemm_backend)
    elif kind == "mlstm":
        a, state = rec.mlstm_block(h, p["cell"], cfg, None)
        x = x + a
        cache_out = state
    elif kind == "slstm":
        a, state = rec.slstm_block(h, p["cell"], cfg, None)
        x = x + a
        cache_out = state
    else:
        raise ValueError(kind)
    return x, aux, cache_out


def apply_block_prefill(x, p, kind: str, cfg: ModelConfig, positions, cache_template):
    """Like apply_block but materializes a decode cache into cache_template.

    Recurrent kinds pass the template through the cell so the returned
    state tree has identical structure/dtypes; attention kinds write the
    fresh K/V (or MLA latents) into the template buffer (ring-rolled for
    local attention so decode's ``pos % window`` slotting lines up).
    """
    kind_e = effective_kind(kind, cfg)
    if kind_e in ("rglru", "mlstm", "slstm"):
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        cell = {"rglru": rec.rglru_block, "mlstm": rec.mlstm_block, "slstm": rec.slstm_block}[kind_e]
        a, state = cell(h, p["cell"], cfg, cache_template)
        x = x + a
        if kind_e == "rglru":
            h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
            x = x + glu_mlp(h2, p["mlp"], cfg.act, cfg.quant_mode, backend=cfg.gemm_backend)
        state = jax.tree_util.tree_map(
            lambda tpl, v: v.astype(tpl.dtype), cache_template, state
        )
        return x, jnp.zeros((), jnp.float32), state

    x, aux, fresh = apply_block(x, p, kind, cfg, positions)
    cache = cache_template
    s = x.shape[1]
    if kind_e in ATTN_KINDS:
        if cfg.uses_mla(kind_e):
            names, vals = ("ckv", "kr"), fresh
        elif cfg.kv_cache_dtype == "int8":
            # quantize fresh K/V into the byte-size cache (+ scale planes)
            kq, ks = attn.quantize_kv(fresh[0])
            vq, vs = attn.quantize_kv(fresh[1])
            names = ("k", "v", "k_scale", "v_scale")
            vals = (kq, vq, ks, vs)
        else:
            names, vals = ("k", "v"), fresh
        for name, val in zip(names, vals):
            buf = cache[name]
            cache_len = buf.shape[1]
            if cache_len >= s:
                buf = jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros(buf.shape, buf.dtype), val.astype(buf.dtype), 0, axis=1
                )
            else:  # local ring: keep the last `cache_len` positions
                buf = val[:, s - cache_len:, :].astype(buf.dtype)
                # ring expects slot order [0..W): roll so slot (pos % W) is correct
                shift = s % cache_len
                buf = jnp.roll(buf, shift, axis=1)
            cache = {**cache, name: buf}
    return x, aux, cache


# ---------------------------------------------------------------------------
# Decode-time single-token application
# ---------------------------------------------------------------------------

def on_layer(paged_fn, cache, layer):
    """``paged_fn(view) -> (out, view)`` on a layer's view of stacked page
    pools: the pools with a leading layer axis, and under ``"layer"`` the
    layer's index into them.  A scanned block's pools are the scan's
    carried stacks at ``layer``; an unscanned block's own pools (``layer``
    None) are a stack of one."""
    if layer is not None:
        out, view = paged_fn({**cache, "layer": layer})
        return out, {k: v for k, v in view.items() if k != "layer"}
    view = {k: v[None] if k in POOL_KEYS else v for k, v in cache.items()}
    out, view = paged_fn({**view, "layer": 0})
    return out, {k: v[0] if k in POOL_KEYS else v
                 for k, v in view.items() if k != "layer"}


def apply_block_decode(x_t, p, kind: str, cfg: ModelConfig, cache, pos,
                       tables=None, active=None, layer=None):
    """One-token decode through one block.  A paged cache is recognized by
    its pool keys (``kp``/``ckvp``); ``tables`` are the block tables
    threaded down from the cache root, ``active`` the live-lane mask (see
    ``model.decode_step``), ``layer`` the block's layer in stacked pools
    (see :func:`scan_pools`).  Per-lane kinds (recurrent state, local-attn
    rings) take the same path in both cache modes."""
    kind = effective_kind(kind, cfg)
    h = rmsnorm(x_t, p["norm1"], cfg.norm_eps)
    if kind in ATTN_KINDS:
        if is_paged(cache):
            paged = (attn.mla_paged_decode if "ckvp" in cache
                     else attn.paged_attention_decode)
            a, cache = on_layer(
                lambda c: paged(h, p["attn"], cfg, c, pos, tables, active=active),
                cache, layer)
        elif cfg.uses_mla(kind):
            a, (ckv, kr) = attn.mla_decode(h, p["attn"], cfg, cache["ckv"], cache["kr"], pos)
            cache = {**cache, "ckv": ckv, "kr": kr}
        else:
            window = cfg.sliding_window if kind == "local_attn" else None
            a, cache = attn.attention_decode(h, p["attn"], cfg, cache, pos, window=window)
        x_t = x_t + a
        h2 = rmsnorm(x_t, p["norm2"], cfg.norm_eps)
        if kind == "moe":
            f, _ = moe_mod.moe_ffn(h2, p["moe"], cfg)
        else:
            f = glu_mlp(h2, p["mlp"], cfg.act, cfg.quant_mode, backend=cfg.gemm_backend)
        x_t = x_t + f
    elif kind == "rglru":
        a, state = rec.rglru_decode(h, p["cell"], cfg, cache)
        x_t = x_t + a
        cache = state
        h2 = rmsnorm(x_t, p["norm2"], cfg.norm_eps)
        x_t = x_t + glu_mlp(h2, p["mlp"], cfg.act, cfg.quant_mode, backend=cfg.gemm_backend)
    elif kind == "mlstm":
        a, state = rec.mlstm_decode(h, p["cell"], cfg, cache)
        x_t = x_t + a
        cache = state
    elif kind == "slstm":
        a, state = rec.slstm_decode(h, p["cell"], cfg, cache)
        x_t = x_t + a
        cache = state
    else:
        raise ValueError(kind)
    return x_t, cache


def apply_block_verify(x, p, kind: str, cfg: ModelConfig, cache, pos,
                       tables=None, active=None, layer=None):
    """W-token speculative verify through one block.

    The verify twin of :func:`apply_block_decode`, restricted to the
    row-independent kinds (``chunkable(cfg)``: attn / mla / dense FFN) —
    MoE is excluded because expert capacity depends on dispatch width, so
    a (B, W) routed FFN could drop different tokens than W sequential
    (B, 1) decodes; recurrent and windowed kinds carry per-step state.
    """
    kind = effective_kind(kind, cfg)
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if kind in ("attn", "mla", "dense_ffn_layer"):
        if is_paged(cache):
            paged = (attn.mla_paged_verify if "ckvp" in cache
                     else attn.paged_attention_verify)
            a, cache = on_layer(
                lambda c: paged(h, p["attn"], cfg, c, pos, tables, active=active),
                cache, layer)
        elif cfg.uses_mla(kind):
            a, (ckv, kr) = attn.mla_verify(h, p["attn"], cfg, cache["ckv"], cache["kr"], pos)
            cache = {**cache, "ckv": ckv, "kr": kr}
        else:
            a, cache = attn.attention_verify(h, p["attn"], cfg, cache, pos)
        x = x + a
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + glu_mlp(h2, p["mlp"], cfg.act, cfg.quant_mode, backend=cfg.gemm_backend)
    else:
        raise ValueError(f"speculative verify unsupported for block kind {kind!r}")
    return x, cache


def scan_pools(period, x, stacked_params, stacked_cache, unroll=1):
    """``lax.scan`` of ``period(h, slot_params, slot_caches, layer) -> (h,
    slot_caches)`` over the stacked periods, the page pools carried.

    Every leaf indexed through ``block_tables`` (``kvcache.POOL_KEYS``)
    rides the loop carry whole, so each layer writes its new rows into the
    stacked pool in place, at ``(layer, page, offset)``, and reads it in
    place at ``layer`` (the period index, from ``xs``).  Passed as
    ``xs``/``ys`` instead, each layer would slice its whole pool out of the
    stack and write it back.  The other leaves (per-lane state, slot
    caches) stay ``xs``/``ys``.
    """
    pools = tuple({k: v for k, v in c.items() if k in POOL_KEYS}
                  for c in stacked_cache)
    rest = tuple({k: v for k, v in c.items() if k not in POOL_KEYS}
                 for c in stacked_cache)
    n_periods = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    def period_fn(carry, xs):
        h, pools = carry
        slot_params, slot_rest, layer = xs
        h, caches = period(h, slot_params,
                           tuple({**r, **c} for r, c in zip(slot_rest, pools)),
                           layer)
        return ((h, tuple({k: c[k] for k in pl} for c, pl in zip(caches, pools))),
                tuple({k: c[k] for k in r} for c, r in zip(caches, slot_rest)))

    (x, pools), rest = jax.lax.scan(
        period_fn, (x, pools), (stacked_params, rest, jnp.arange(n_periods)),
        unroll=unroll)
    return x, tuple({**r, **c} for r, c in zip(rest, pools))


def scan_periods_verify(x, stacked_params, stacked_cache, cfg: ModelConfig, pos,
                        tables=None, active=None):
    from repro.runtime.sharding import constrain_decode_carry

    def period(h, slot_params, slot_cache, layer):
        h = constrain_decode_carry(h)  # TP: see scan_periods_decode
        new_cache = []
        for s, kind in enumerate(cfg.block_pattern):
            h, c = apply_block_verify(h, slot_params[s], kind, cfg, slot_cache[s], pos,
                                      tables=tables, active=active, layer=layer)
            new_cache.append(c)
        return h, tuple(new_cache)

    return scan_pools(period, x, stacked_params, stacked_cache, cfg.scan_unroll)


# ---------------------------------------------------------------------------
# Layer layout: periods + tail
# ---------------------------------------------------------------------------

def layer_layout(cfg: ModelConfig, n_layers=None):
    """(first_k_dense, n_periods, tail_kinds) for the given depth."""
    n = n_layers if n_layers is not None else cfg.n_layers
    lead = cfg.moe.first_k_dense if cfg.moe is not None else 0
    rest = n - lead
    period = cfg.pattern_period
    n_periods = rest // period
    tail_kinds = tuple(cfg.block_pattern[i % period] for i in range(n_periods * period, rest))
    return lead, n_periods, tail_kinds


def scan_periods(x, stacked_params, cfg: ModelConfig, positions, *, causal=True):
    """Run n_periods x pattern via lax.scan. stacked_params: tuple per slot."""
    from repro.runtime.sharding import constrain_activations

    pattern = cfg.block_pattern

    def period_fn(carry, slot_params):
        h, aux = carry
        h = constrain_activations(h)  # SP: carry saved seq-sharded for bwd
        # barrier: stops XLA hoisting the rmsnorm f32 upcast across the
        # remat boundary (it would store the carry stack at 2x bytes)
        h = jax.lax.optimization_barrier(h)
        for s, kind in enumerate(pattern):
            h, a, _ = apply_block(h, slot_params[s], kind, cfg, positions, causal=causal)
            aux = aux + a
        return (h, aux), None

    if cfg.remat:
        # "nothing": save NOTHING inside a period — the scan stores exactly
        # the bf16 carry per layer-period (min memory, full recompute).
        # "dots": save matmul/einsum outputs — bwd recomputes only the
        # elementwise ops (±0 extra MXU flops, more activation memory).
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat_policy == "nothing"
                  else jax.checkpoint_policies.checkpoint_dots)
        period_fn = jax.checkpoint(period_fn, policy=policy, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(period_fn, (x, jnp.zeros((), jnp.float32)), stacked_params,
                               unroll=cfg.scan_unroll)
    return x, aux


def scan_periods_decode(x_t, stacked_params, stacked_cache, cfg: ModelConfig, pos,
                        tables=None, active=None):
    from repro.runtime.sharding import constrain_decode_carry

    def period(h, slot_params, slot_cache, layer):
        # TP: pin the (B, 1, d) carry replicated-over-model between periods
        # so the partitioner never round-trips it through sharded layouts
        h = constrain_decode_carry(h)
        new_cache = []
        for s, kind in enumerate(cfg.block_pattern):
            # tables/active are loop-invariant captures: every period indexes
            # its own layer of the carried pools through the same tables
            h, c = apply_block_decode(h, slot_params[s], kind, cfg, slot_cache[s], pos,
                                      tables=tables, active=active, layer=layer)
            new_cache.append(c)
        return h, tuple(new_cache)

    return scan_pools(period, x_t, stacked_params, stacked_cache, cfg.scan_unroll)


def scan_periods_prefill(x, stacked_params, stacked_cache_tpl, cfg: ModelConfig, positions):
    pattern = cfg.block_pattern

    def period_fn(carry, xs):
        h, aux = carry
        slot_params, slot_tpl = xs
        new_cache = []
        for s, kind in enumerate(pattern):
            h, a, c = apply_block_prefill(h, slot_params[s], kind, cfg, positions, slot_tpl[s])
            aux = aux + a
            new_cache.append(c)
        return (h, aux), tuple(new_cache)

    (x, aux), new_cache = jax.lax.scan(
        period_fn, (x, jnp.zeros((), jnp.float32)), (stacked_params, stacked_cache_tpl),
        unroll=cfg.scan_unroll,
    )
    return x, aux, new_cache
