"""The ``mla_moe`` family (DeepSeek-V2-Lite) at a size a test run holds.

The configuration file's own keys, at tiny widths: latent attention with
YaRN and a direct query projection in a leading dense layer and two MoE
layers, each holding 4 of the router's 8 experts (from expert 2) beside
two shared experts.  The reference draws the weights the program was
given; the sound program is ``correct``; its int4 control is not; nor is
the program with a planted fault: plain rope in place of YaRN, the top-k
weights renormalised, a held expert's output dropped, or the new latent
row left unwritten.  The two readers read hand-worked bytes and scope
seconds.
"""

import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import check, spec

CHIP = Path(__file__).resolve().parent
SEED = 2 ** 33 + 5
FILE = json.loads((CHIP / "configs" / "dsv2lite-e8-int8.json").read_text())
MODEL = dict(FILE["model"], hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3,
             vocab_size=512, router_experts=8, n_routed_experts=4, first_held_expert=2,
             num_experts_per_tok=2)
# readings at this size and SPREAD (widest logit gap over a sample of
# ~400 served tokens, seeds SEED to SEED + 11): program 0.076-0.257,
# control 0.977-1.822; the limit sits between, about twice each way
LIMIT = 0.5
RUNTIME = {"quant": {"mode": "int8_spoga"},
           "kv": {"mode": "paged", "dtype": "bf16", "cache_len": 152},
           "scheduler": {"n_slots": 4, "prefill_buckets": [16, 32, 64, 128]}}
CONFIG = {"family": "mla_moe", "model": MODEL, "check": {"max_logit_gap": LIMIT},
          "program": dict(FILE["program"], runtime=RUNTIME)}
MIX = {"kind": "open_loop", "rate_rps": 40.0, "stream": True,
       "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.9, "min": 8, "max": 120},
       "output": {"dist": "lognormal", "median": 12, "sigma": 0.8, "min": 4, "max": 24},
       "check_tokens": 400}
WINDOW_S = 1.5
# every product's weights at 0.02 * sqrt(2048 / 128), so that its outputs
# spread as at the published widths
SPREAD = 0.08


@pytest.fixture(scope="module")
def run_mod():
    spec_ = importlib.util.spec_from_file_location("chipbench_run", CHIP / "run.py")
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


@pytest.fixture
def cell(monkeypatch):
    """The tiny cell, its products drawn at std ``SPREAD``: at 0.02 a
    128-wide model's scores and routing are so flat that neither rope nor
    an expert moves a logit past the int8 rounding."""
    from reference import common, mla_moe

    peaks = spec.peaks_for("TPU v5 lite")
    monkeypatch.setattr(spec, "peaks_for", lambda kind: peaks)
    monkeypatch.setattr(mla_moe, "LINEAR_STD", SPREAD)
    monkeypatch.setattr(common, "LINEAR_STD", SPREAD)
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    return spec.Cell("dsv2lite-longchat", "tiny-mla-moe", "tiny", 1, CONFIG, MIX, bench)


@pytest.fixture
def fresh_programs():
    """The engine caches its jitted programs per configuration; a planted
    fault needs fresh traces, and must not leak into other tests."""
    from repro.serving import engine

    def clear():
        for obj in vars(engine).values():
            if callable(getattr(obj, "cache_clear", None)):
                obj.cache_clear()

    clear()
    yield engine
    clear()


def test_the_file_states_the_published_config_it_runs():
    """The top-level copy of the published config and ``model`` agree;
    the flat rope keys repeat ``rope_scaling``; the program is built with
    8 held experts of a 64-wide router and YaRN."""
    from reference import mla_moe

    model = FILE["model"]
    for key, value in FILE.items():
        if key in model:
            assert model[key] == value, key
    assert mla_moe.layer_kinds(model) == ["dense"] + ["moe"] * 26
    cfg = spec.model_config(spec.load_cell("dsv2lite-longchat"))
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.first_held) == (64, 8, 0)
    assert cfg.moe.top_k == 6 and not cfg.moe.norm_topk_prob
    assert cfg.mla.q_lora_rank is None and cfg.rope_scaling.factor == 40.0
    assert cfg.moe.capacity_factor * cfg.moe.top_k == pytest.approx(64)
    with pytest.raises(ValueError, match="rope_scaling_factor"):
        mla_moe.layer_kinds(dict(model, rope_scaling_factor=32))


def test_reference_draws_the_weights_the_program_was_given(cell):
    """The reference draws every leaf again from the seed and gets what
    the program holds, wherever it keeps the layer; the program's rope
    columns are the published interleaved pairs, evens then odds."""
    from chipbench import weights as W
    from repro.models.model import param_shapes

    params = cell.program_params(SEED, param_shapes(spec.model_config(cell)))
    root = W.root_key(W.seed_words(SEED))
    halves = jnp.array([0, 2, 4, 6, 1, 3, 5, 7])
    for layer, kind in enumerate(["dense", "moe", "moe"]):
        ref = W.layer_weights(root, cell.reference.layer_specs(MODEL, kind), layer)
        blk = (params["head_blocks"][0] if layer == 0 else
               jax.tree_util.tree_map(lambda x, q=layer - 1: x[q], params["blocks"][0]))
        for name, leaf in ref.items():
            got = blk
            for key in name.split("/"):
                got = got[key]
            if name == "attn/w_q":
                leaf = leaf.reshape(128, 4, 24)
                leaf = jnp.concatenate([leaf[..., :16], leaf[..., 16:][..., halves]], -1)
                leaf = leaf.reshape(128, 96)
            if name == "attn/w_kr":
                leaf = leaf[:, halves]
            assert jnp.array_equal(got, leaf), name
        if kind == "moe":
            assert blk["moe"]["experts_gate"].shape[0] == 4


def test_sound_program_is_correct_and_control_is_not(run_mod, cell):
    res = run_mod.run_cell(cell, SEED, WINDOW_S, False, jax.devices())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}
    _, engine, (tracked, _, _, _) = run_mod.serve(cell, SEED, WINDOW_S)
    assert engine.metrics.report()["mla_rows_live"] > 0
    pairs = run_mod.check_pairs(cell, engine, tracked, SEED)
    g = check.gaps("mla_moe", MODEL, SEED, pairs, quant_ctrl="w4a8")
    assert g["served"].max() <= LIMIT < g["control"].max()


def _plain_rope(layout):
    sound = layout.model_config
    return lambda conf: sound(conf).with_(rope_scaling=None)


def _renormalised(layout):
    sound = layout.model_config

    def planted(conf):
        cfg = sound(conf)
        return cfg.with_(moe=dataclasses.replace(cfg.moe, norm_topk_prob=True))

    return planted


def _expert_dropped(moe):
    sound = moe.moe_ffn

    def planted(x, p, cfg):
        return sound(x, dict(p, experts_down=p["experts_down"].at[0].set(0)), cfg)

    return planted


def _latent_row_unwritten(attn):
    """Paged decode whose new latent row goes to the trash page, as an
    idle lane's does: the lane attends whatever its page held there."""
    sound = attn.mla_paged_decode

    def planted(x_t, p, cfg, cache, pos, tables, *, active=None):
        return sound(x_t, p, cfg, cache, pos, tables, active=jnp.zeros_like(active))

    return planted


@pytest.mark.parametrize("fault", ["plain_rope", "renormalised", "expert_dropped",
                                   "latent_row_unwritten"])
def test_planted_fault_is_not_correct(run_mod, cell, fresh_programs, monkeypatch, fault):
    from repro.models import attention, moe

    layout = cell.layout
    if fault == "plain_rope":
        monkeypatch.setattr(layout, "model_config", _plain_rope(layout))
    elif fault == "renormalised":
        monkeypatch.setattr(layout, "model_config", _renormalised(layout))
    elif fault == "expert_dropped":
        monkeypatch.setattr(moe, "moe_ffn", _expert_dropped(moe))
    else:
        monkeypatch.setattr(attention, "mla_paged_decode", _latent_row_unwritten(attention))
    res = run_mod.run_cell(cell, SEED + 1, WINDOW_S, False, jax.devices())
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT


def _reader(name):
    return spec.load_reader(name)


def _record(events, scopes):
    m = FILE["model"]
    steps = [{"t0": 1.0, "t1": 1.1, "decode_keys": [4096, 1000]},
             {"t0": 1.2, "t1": 1.3, "decode_keys": [4097]},
             {"t0": 5.0, "t1": 5.1, "decode_keys": [9999]}]      # after the slice
    return {"model": m, "steps": steps, "slice": [0.5, 2.0],
            "peaks": spec.peaks_for("TPU v5 lite"),
            "trace": {"events": events, "scopes": scopes, "busy_s": 0.2}}


def test_readers_by_hand():
    """Two decode steps in the slice attend 5096 and 4097 latent rows a
    layer: 9193 rows x 27 layers x (512 + 64) x 2 B = 285,939,072 B,
    0.349132 ms at 819 GB/s.  Ops under ``mla_attn`` took 3.5 ms, those
    under ``moe_experts`` 0.05 of the 0.2 s busy."""
    events = [("%fusion.1", 0.0015, ""), ("%gather.2", 0.002, ""),
              ("%fusion.3", 0.05, ""), ("%fusion.4", 0.1, "")]
    scopes = {"%fusion.1": "jit(step)/while/body/mla_attn/exp",
              "%gather.2": "jit(step)/while/body/mla_attn/gather",
              "%fusion.3": "jit(step)/while/body/moe_experts/weight_quant/abs",
              "%fusion.4": "jit(step)/while/body/weight_quant/abs"}
    rec = _record(events, scopes)
    least = 9193 * 27 * 576 * 2 / 819e9
    assert _reader("mla_attn_roofline.chat")(rec) == pytest.approx(100 * least / 0.0035)
    assert _reader("mla_attn_roofline.chat")(rec) == pytest.approx(9.9752, abs=1e-4)
    assert _reader("experts_share.chat")(rec) == pytest.approx(25.0)
    # a program without the scopes (the parent's) reads nothing
    bare = _record(events, {})
    assert _reader("mla_attn_roofline.chat")(bare) is None
    assert _reader("experts_share.chat")(bare) is None
    assert _reader("mla_attn_roofline.chat")(dict(rec, trace=None)) is None
