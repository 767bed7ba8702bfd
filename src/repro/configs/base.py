"""Model / run configuration dataclasses.

One ``ModelConfig`` describes any of the 10 assigned architectures (dense /
MoE / MLA / SSM / hybrid / enc-dec / stub-frontend) plus the SPOGA
quantization execution mode.  Configs are plain frozen dataclasses so they
hash (static jit args) and serialize trivially.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# ---------------------------------------------------------------------------
# Quantization execution modes (DESIGN.md §3) — canonical list lives next to
# the QuantSpec parser so configs and backends cannot drift apart.
from repro.backends.spec import QUANT_MODES, parse_quant_mode


# ---------------------------------------------------------------------------
# KV-cache sizing policy (shared by the static server, the continuous-batching
# engine and the benchmarks so they always agree on cache shapes).
#
# Headroom beyond prompt + generation covers (a) speculative/extra decode
# steps past a request's nominal budget and (b) rounding prompt lengths up to
# a prefill bucket — without it every off-by-one re-allocates (and re-jits)
# the cache. 8 slots is < 1% overhead at serving lengths.
KV_CACHE_HEADROOM = 8


def default_cache_len(prompt_len: int, gen_tokens: int,
                      headroom: int = KV_CACHE_HEADROOM) -> int:
    """Cache length for serving ``prompt_len`` + ``gen_tokens`` decode steps."""
    return prompt_len + gen_tokens + headroom


# Paged KV-cache policy (repro/paging/). A page holds PAGE_SIZE token rows;
# 16 keeps per-request internal fragmentation under one MXU tile while the
# byte-size int8 page (16 x H x D int8 + scales) stays a few KiB — small
# enough that mixed-length traffic packs the pool tightly.
DEFAULT_PAGE_SIZE = 16


def pages_for(tokens: int, page_size: int = DEFAULT_PAGE_SIZE) -> int:
    """Pages covering ``tokens`` cache rows (ceil division)."""
    return -(-max(int(tokens), 0) // page_size)


def default_page_count(n_lanes: int, cache_len: int,
                       page_size: int = DEFAULT_PAGE_SIZE) -> int:
    """Pool size matching the slot-cache KV budget: ``n_lanes`` worst-case
    requests, plus the reserved trash page 0 (see paging/manager.py)."""
    return n_lanes * pages_for(cache_len, page_size) + 1


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0          # routed experts
    top_k: int = 0
    num_shared_experts: int = 0   # DeepSeekMoE-style always-on experts
    d_expert: int = 0             # per-expert FFN hidden size
    capacity_factor: float = 1.25
    first_k_dense: int = 0        # leading layers that use a dense FFN
    d_ff_dense: int = 0           # hidden size of those dense FFN layers
    # renormalise the top-k router weights over the chosen experts
    # (Mixtral, Granite); DeepSeek-V2 keeps the full softmax's weights
    norm_topk_prob: bool = True
    # expert parallelism: this device holds routed experts
    # [first_held, first_held + n_held) of the router's num_experts and
    # computes only their part of the layer (0 = every expert)
    n_held: int = 0
    first_held: int = 0

    def __post_init__(self):
        if self.first_held < 0 or self.first_held + self.held > self.num_experts:
            raise ValueError(
                f"held experts [{self.first_held}, {self.first_held + self.held}) "
                f"must lie within the router's {self.num_experts}")

    @property
    def held(self) -> int:
        return self.n_held or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: Optional[int] = 768     # None: a direct d -> H * qk_dim query
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN context extension of rotary embeddings (DeepSeek-V2's
    ``rope_scaling``): inverse frequencies blended between interpolated and
    original over a linear ramp between the dims that ``beta_fast`` and
    ``beta_slow`` rotations fix, cos/sin scaled by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` and the
    attention scale by ``mscale(factor, mscale_all_dim) ** 2``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | vlm | audio | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None       # defaults to d_model // n_heads
    # block pattern, cycled through the stack; entries in
    # {"attn", "local_attn", "moe", "mlstm", "slstm", "rglru"}
    block_pattern: tuple = ("attn",)
    # attention
    sliding_window: Optional[int] = None  # for local_attn blocks
    rope_theta: float = 10_000.0
    rope_scaling: Optional[RopeScaling] = None
    use_mla: bool = False
    mla: Optional[MLAConfig] = None
    # MoE
    moe: Optional[MoEConfig] = None
    # recurrent (rglru / xlstm)
    conv_width: int = 4
    lru_width: Optional[int] = None
    # encoder-decoder
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    # modality frontend stub: None | "vision" | "audio"
    frontend: Optional[str] = None
    # numerics
    quant_mode: str = "bf16"
    # GEMM backend registry name ("jnp_spoga", "pallas_spoga_dequant",
    # "pallas_interpret", ...); None = auto-select by platform/family.
    gemm_backend: Optional[str] = None
    norm_eps: float = 1e-6
    act: str = "silu"
    tie_embeddings: bool = False
    # scan/remat
    scan_layers: bool = True
    remat: bool = True
    # "nothing"  — recompute the whole period in bwd (min memory, +33% flops)
    # "dots"     — save matmul outputs, recompute elementwise only
    remat_policy: str = "nothing"
    # KV cache storage dtype for decode: "bf16" | "int8" (SPOGA-sliced
    # storage: int8 payload + per-(pos, head) scale; halves cache HBM reads)
    kv_cache_dtype: str = "bf16"
    # Paged-attention implementation for block-table decode (repro/paging/):
    # None = auto (Pallas kernel on TPU, jnp gather twin elsewhere);
    # "jnp" | "pallas" | "pallas_interpret" force a path (interpret covers
    # the kernel body in CI, mirroring the GEMM backends).
    paged_attn_impl: Optional[str] = None
    # Fully unroll every lax.scan (layers + loss chunks). Used by the
    # dry-run's cost-calibration pass: XLA's HloCostAnalysis counts a
    # while-loop body ONCE (not x trip count), so scanned stacks would
    # under-report flops/bytes/collectives by ~n_layers. Never enable for
    # real execution of deep configs (compile time is O(depth)).
    scan_unroll: bool = False
    # Numerics watchdog (repro.obs.watchdog): when set, every quantized
    # GEMM stages per-layer saturation/amax/quant-error stats through
    # jax.debug.callback. Lives on ModelConfig (not a global) so every
    # lru_cached jit wrapper in the engine re-keys when it toggles —
    # a compiled trace can never be reused across watchdog states.
    numerics_watchdog: bool = False

    def __post_init__(self):
        if self.quant_mode not in QUANT_MODES:
            # Parametric modes ("w4a8", "w8a8_s2", ...) validate via the
            # spec parser; anything it rejects is a genuine config error.
            try:
                parse_quant_mode(self.quant_mode)
            except ValueError:
                raise ValueError(
                    f"quant_mode must be in {QUANT_MODES} or a parametric "
                    f"'w<bits>a<bits>[_s<slice>]' string, got {self.quant_mode!r}"
                ) from None
        if self.gemm_backend is not None:
            # Touching the registry loads the kernel stack (jax + Pallas);
            # only pay that when a backend override is actually configured.
            from repro.backends import get_backend

            get_backend(self.gemm_backend)  # raises KeyError on unknown names
        if self.paged_attn_impl not in (None, "jnp", "pallas", "pallas_interpret"):
            raise ValueError(
                "paged_attn_impl must be None (auto), 'jnp', 'pallas' or "
                f"'pallas_interpret', got {self.paged_attn_impl!r}")
        if self.family == "moe" and self.moe is None:
            raise ValueError("moe family requires moe config")
        if self.rope_scaling is not None and not self.use_mla:
            raise ValueError("rope_scaling (YaRN) is implemented for latent "
                             "attention (use_mla) only")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def pattern_period(self) -> int:
        return len(self.block_pattern)

    def uses_mla(self, kind: str) -> bool:
        """Whether a block of (effective) ``kind`` attends through latent
        attention: every full-attention block of an MLA model, its MoE
        and leading dense blocks included (DeepSeek-V2)."""
        return self.use_mla and kind in ("attn", "mla", "moe", "dense_ffn_layer")

    def layer_type(self, i: int) -> str:
        if self.moe is not None and i < self.moe.first_k_dense:
            return "dense_ffn_layer"
        return self.block_pattern[i % len(self.block_pattern)]

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str           # train_4k | prefill_32k | decode_32k | long_500k
    kind: str           # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1000
    schedule: str = "cosine"      # cosine | linear | constant
    zero1: bool = True            # shard optimizer state over the data axis
    fsdp: bool = True             # ZeRO-3 weight sharding over the data axis
    microbatches: int = 1         # gradient accumulation steps per update
    grad_compression: bool = False  # int8 compressed gradient all-reduce
    # dtype of the gradient reduce-scatter payload: "f32" (exact) or
    # "bf16" (halves the dominant DP collective; AdamW still updates the
    # f32 master copy)
    grad_reduce_dtype: str = "f32"
