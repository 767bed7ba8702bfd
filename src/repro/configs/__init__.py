"""Config registry: one module per assigned architecture (+ paper's CNNs).

``get_config("mistral-large-123b")`` returns the full published config;
``reduced(cfg)`` returns a smoke-test-sized config of the same family.
"""

from __future__ import annotations

import dataclasses

from repro.configs.base import (
    DEFAULT_PAGE_SIZE,
    KV_CACHE_HEADROOM,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SHAPES,
    ShapeConfig,
    default_cache_len,
    default_page_count,
    pages_for,
)

from repro.configs import (
    mistral_large_123b,
    minicpm3_4b,
    mistral_nemo_12b,
    llama32_1b,
    granite_moe_3b,
    deepseek_moe_16b,
    deepseek_v2_lite,
    xlstm_125m,
    llava_next_mistral_7b,
    seamless_m4t_large_v2,
    recurrentgemma_9b,
)

ARCHS = {
    "mistral-large-123b": mistral_large_123b.CONFIG,
    "minicpm3-4b": minicpm3_4b.CONFIG,
    "mistral-nemo-12b": mistral_nemo_12b.CONFIG,
    "llama3.2-1b": llama32_1b.CONFIG,
    "granite-moe-3b-a800m": granite_moe_3b.CONFIG,
    "deepseek-moe-16b": deepseek_moe_16b.CONFIG,
    "deepseek-v2-lite": deepseek_v2_lite.CONFIG,
    "xlstm-125m": xlstm_125m.CONFIG,
    "llava-next-mistral-7b": llava_next_mistral_7b.CONFIG,
    "seamless-m4t-large-v2": seamless_m4t_large_v2.CONFIG,
    "recurrentgemma-9b": recurrentgemma_9b.CONFIG,
}

# archs with *bounded-state* sequence mixing run the 500k-decode cell;
# pure full-attention archs skip it (DESIGN.md §Arch-applicability).
SUBQUADRATIC = ("xlstm-125m", "recurrentgemma-9b")


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def cells(include_skipped: bool = False):
    """All assigned (arch x shape) dry-run cells. 40 total, 34 runnable."""
    out = []
    for arch in ARCHS:
        for shape in SHAPES.values():
            skipped = shape.name == "long_500k" and arch not in SUBQUADRATIC
            if skipped and not include_skipped:
                continue
            out.append((arch, shape.name, skipped))
    return out


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Smoke-test config of the same family: tiny dims, same block pattern."""
    period = cfg.pattern_period
    lead = cfg.moe.first_k_dense if cfg.moe else 0
    n_layers = lead + 2 * period + (1 if cfg.name == "recurrentgemma-9b" else 0)
    kw = dict(
        n_layers=n_layers,
        d_model=128,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads > 1 else 1,
        head_dim=32,
        d_ff=0 if cfg.d_ff == 0 else 256,
        vocab_size=512,
        lru_width=128 if cfg.lru_width else None,
        sliding_window=32 if cfg.sliding_window else None,
        n_encoder_layers=2 if cfg.is_encoder_decoder else 0,
    )
    if cfg.moe is not None:
        kw["moe"] = MoEConfig(
            num_experts=8,
            top_k=2,
            num_shared_experts=cfg.moe.num_shared_experts,
            d_expert=64,
            # high capacity -> no token drops at smoke scale, so the
            # prefill+decode path is exactly consistent with full forward
            # (capacity is per-sequence; different S would otherwise drop
            # different tokens)
            capacity_factor=8.0,
            first_k_dense=cfg.moe.first_k_dense,
            d_ff_dense=256 if cfg.moe.d_ff_dense else 0,
            norm_topk_prob=cfg.moe.norm_topk_prob,
        )
    if cfg.use_mla:
        kw["mla"] = MLAConfig(
            q_lora_rank=64 if cfg.mla.q_lora_rank else None, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        )
    return dataclasses.replace(cfg, **kw)
