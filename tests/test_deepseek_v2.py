"""DeepSeek-V2-Lite's pieces at a tiny size: latent attention without a
query LoRA, with YaRN rope, inside MoE and leading dense blocks; a router
whose top-k weights are not renormalised; an expert layer told which
experts it holds; their HLO scopes and the engine's latent-row counters;
and the paged engine's prefill and decode against the model's full
forward.  Granite's MoE, on the defaults, is held to the outputs it gave
before any of this existed.
"""

import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import MoEConfig, RopeScaling
from repro.models import forward, init_params
from repro.models.attention import mla_softmax_scale
from repro.models.layers import glu_mlp, rope_attention_factor, rope_frequencies, yarn_mscale
from repro.models.model import decode_step, paged_cache_shapes, param_shapes
from repro.models.moe import _route, moe_ffn
from repro.serving import EngineConfig, ServingEngine

YARN = RopeScaling(factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def _tiny(**kw):
    return reduced(get_config("deepseek-v2-lite")).with_(remat=False, **kw)


def _hf_yarn_inv_freq(dim, base, rs):
    """DeepSeek-V2's published ``DeepseekV2YarnRotaryEmbedding`` inverse
    frequencies, written out in numpy float64."""
    def corr_dim(rot):
        return dim * math.log(rs.original_max_position_embeddings / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr_dim(rs.beta_fast)), 0)
    high = min(math.ceil(corr_dim(rs.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (rs.factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


@pytest.mark.parametrize("dim", [64, 8])
def test_yarn_frequencies_and_mscale_match_the_closed_form(dim):
    got = np.asarray(rope_frequencies(dim, 10_000.0, YARN), np.float64)
    want = _hf_yarn_inv_freq(dim, 10_000.0, YARN)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the fastest dims keep the original frequency, the slowest are
    # interpolated 40-fold
    plain = np.asarray(rope_frequencies(dim, 10_000.0), np.float64)
    assert got[0] == pytest.approx(plain[0]) and got[-1] == pytest.approx(plain[-1] / 40)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(0.1 * 0.707 * math.log(40.0) + 1.0)
    assert yarn_mscale(1.0, 0.707) == 1.0
    # mscale == mscale_all_dim: cos and sin are not scaled
    assert rope_attention_factor(YARN) == 1.0
    assert rope_attention_factor(RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                                             mscale=1.0, mscale_all_dim=0.5)) == pytest.approx(
        yarn_mscale(40.0, 1.0) / yarn_mscale(40.0, 0.5))


def test_softmax_scale_takes_yarns_mscale_squared():
    full = get_config("deepseek-v2-lite")
    assert mla_softmax_scale(full) == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    assert mla_softmax_scale(full) == pytest.approx(0.1147, abs=1e-4)
    plain = get_config("minicpm3-4b")
    assert mla_softmax_scale(plain) == (64 + 32) ** -0.5
    with pytest.raises(ValueError, match="latent attention"):
        get_config("llama3.2-1b").with_(rope_scaling=YARN)


def test_direct_query_projection_has_no_q_lora():
    """``q_lora_rank`` None: one d -> H x 192 query projection, in every
    block kind (the dense lead and the MoE layers alike)."""
    full = get_config("deepseek-v2-lite")
    shapes = param_shapes(full.with_(moe=MoEConfig(**{
        **full.moe.__dict__, "n_held": 8, "first_held": 0})))
    lead = shapes["head_blocks"][0]
    moe = shapes["blocks"][0]
    lead_attn = {k: v.shape for k, v in lead["attn"].items()}
    moe_attn = {k: v.shape[1:] for k, v in moe["attn"].items()}   # stacked over 26
    for attn in (lead_attn, moe_attn):
        assert set(attn) == {"w_q", "w_dkv", "kv_norm", "w_kr", "w_uk", "w_uv", "wo"}
        assert attn["w_q"] == (2048, 16 * 192)
        assert attn["w_dkv"] == (2048, 512) and attn["w_kr"] == (2048, 64)
        assert attn["w_uk"] == (512, 16 * 128) and attn["w_uv"] == (512, 16 * 128)
    assert lead["mlp"]["w_gate"].shape == (2048, 10944)
    # 26 MoE layers: the router spans 64 experts, 8 are held, the two
    # shared experts are one GLU of twice the expert width
    assert moe["moe"]["router"].shape == (26, 2048, 64)
    assert moe["moe"]["experts_gate"].shape == (26, 8, 2048, 1408)
    assert moe["moe"]["shared"]["w_gate"].shape == (26, 2048, 2816)
    assert "w_dq" in param_shapes(get_config("minicpm3-4b"))["blocks"][0]["attn"]


def test_norm_topk_prob_false_keeps_the_softmax_weights():
    m = MoEConfig(num_experts=8, top_k=3, norm_topk_prob=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 16))
    p = {"router": jax.random.normal(jax.random.PRNGKey(1), (16, 8))}
    probs, topw, topi = _route(x, p, m)
    want = jnp.take_along_axis(jax.nn.softmax(x @ p["router"], axis=-1), topi, axis=-1)
    np.testing.assert_allclose(topw, want, rtol=1e-6)
    assert float(topw.sum(-1).max()) < 1.0
    _, renorm, _ = _route(x, p, MoEConfig(num_experts=8, top_k=3))
    np.testing.assert_allclose(renorm.sum(-1), 1.0, rtol=1e-6)


def test_expert_shares_sum_to_the_uncut_layer():
    """Expert parallelism over 8 devices, one routed expert each: the
    routed parts of all shares, with the shared experts (which every
    device computes alike) counted once, add up to the whole layer."""
    cfg = _tiny()
    moe = jax.tree_util.tree_map(lambda a: a[0], init_params(cfg, jax.random.PRNGKey(0))["blocks"][0])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model), jnp.float32)
    whole, _ = moe_ffn(x, moe, cfg)
    shared = glu_mlp(x, moe["shared"], cfg.act)
    m = cfg.moe
    total = jnp.zeros_like(whole)
    for i in range(m.num_experts):
        share_cfg = cfg.with_(moe=MoEConfig(**{**m.__dict__, "n_held": 1, "first_held": i}))
        held = {k: (v[i:i + 1] if k.startswith("experts_") else v) for k, v in moe.items()}
        out, _ = moe_ffn(x, held, share_cfg)
        total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole), rtol=1e-5, atol=1e-6)
    # a share's part is not the whole: the other devices' experts matter
    assert float(jnp.abs(out - whole).max()) > 1e-3


# moe_ffn on one block of the tiny granite (seeded weights and inputs),
# sha256 of its output and aux loss, as the layer read before it learnt
# held experts and unrenormalised routing
GRANITE_MOE_SHA256 = {
    "bf16": "fd6c3210c2d6577396cd93ca6b1198ef0479f778a81bd7265a4e9c4bae46e9b4",
    "int8_spoga": "a034708e054db930d917e66b86df4db4909750419e275dcdb3e89abba5615794",
}


@pytest.mark.parametrize("quant_mode", sorted(GRANITE_MOE_SHA256))
def test_granite_moe_is_bitwise_unchanged_on_the_defaults(quant_mode):
    cfg = reduced(get_config("granite-moe-3b-a800m")).with_(remat=False, quant_mode=quant_mode)
    p = jax.tree_util.tree_map(lambda a: a[0], init_params(cfg, jax.random.PRNGKey(0))["blocks"][0])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.d_model), jnp.float32).astype(jnp.bfloat16)
    out, aux = jax.jit(lambda x, p: moe_ffn(x, p, cfg))(x, p["moe"])
    digest = hashlib.sha256(np.asarray(out.astype(jnp.float32)).tobytes()
                            + np.asarray(aux).tobytes()).hexdigest()
    assert digest == GRANITE_MOE_SHA256[quant_mode]


def test_latent_attention_and_routed_experts_are_named_in_the_hlo():
    """The paged decode step carries ``mla_attn`` on the latent row write,
    the page gather and the softmax, and ``moe_experts`` on the router and
    the held experts' grouped GEMMs with their re-quantization."""
    cfg = _tiny(quant_mode="int8_spoga")
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                   paged_cache_shapes(cfg, 2, 32, 8, 9))
    tokens = jnp.zeros((2,), jnp.int32)
    text = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c)).lower(
        params, tokens, cache).as_text(debug_info=True)
    names = {re.sub(r"^jit\(<lambda>\)/", "", n)
             for n in re.findall(r'loc\("([^"]*)"', text)}
    assert {"mla_attn/scatter", "mla_attn/gather", "mla_attn/exp",
            "mla_attn/bchl,bsl->bchs/dot_general"} <= names
    assert {"moe_experts/bsd,de->bse/dot_general", "moe_experts/top_k",
            "moe_experts/vmap(jit(argsort))", "moe_experts/weight_quant/reduce_max",
            "moe_experts/vmap(skd,sk->sd)/dot_general"} <= names
    # the shared experts' GEMMs (and the projections) re-quantize outside it
    assert "weight_quant/reduce_max" in names


def _engine(cfg, params, **kw):
    return ServingEngine(cfg, params, EngineConfig(
        n_slots=2, cache_len=48, prefill_buckets=(8, 16, 32), cache_mode="paged",
        page_size=8, **kw))


def test_paged_engine_agrees_with_the_full_forward():
    """Prefill and then decode through the engine's paged latent pool: at
    every served position the full forward's logit of the served token lies
    within rounding of its best (bf16 activations on both paths, the engine
    in the absorbed latent form, the forward in the explicit one)."""
    cfg = _tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = _engine(cfg, params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (5, 13, 3, 17)]
    gens = [6, 4, 8, 5]
    metrics = engine.run([(0, prompts[0], gens[0]), (0, prompts[1], gens[1]),
                          (2, prompts[2], gens[2]), (4, prompts[3], gens[3])])
    by_id = {r.req_id: r.output_tokens for r in metrics.finished}
    widest = 0.0
    for i, (p, g) in enumerate(zip(prompts, gens)):
        out = by_id[i]
        assert len(out) == g
        seq = jnp.asarray([p + out[:-1]], jnp.int32)
        logits = np.asarray(forward(params, cfg, {"tokens": seq})[0], np.float32)
        rows = logits[len(p) - 1:]
        gap = rows.max(-1) - rows[np.arange(g), out]
        widest = max(widest, float(gap.max()))
    # logits here are ~0.3 apart at the top; rounding moves them ~1e-2
    assert widest <= 0.02, widest
    assert engine.store.manager.pages_in_use == 0


def test_engine_counts_latent_rows():
    """Per decode step and layer: the rows each running lane attends (its
    context and the row being written) against every lane's whole table
    (2 lanes x 6 pages of 8)."""
    cfg = _tiny()
    engine = _engine(cfg, init_params(cfg, jax.random.PRNGKey(0)))
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 11).tolist()
    rep = engine.run([(0, prompt, 6)]).report()
    assert rep["decode_steps"] == 5
    # decode step j (1..5) attends the 11 prompt rows and j tokens
    assert rep["mla_rows_live"] == sum(11 + j for j in range(1, 6))
    assert rep["mla_rows_gathered"] == 5 * 2 * 48
    assert rep["mla_live_share"] == round(rep["mla_rows_live"] / 480, 4)
    assert rep["attn_blocks_walked"] == 0
