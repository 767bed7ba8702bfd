"""The comparison that decides ``correct``, at a size a test run holds.

Tiny configurations of the benchmark's two families (the program's
``int8_spoga`` GEMMs and int8 paged cache, jnp twins on the CPU; dense
GQA, and GQA with a dropless top-k MoE, also behind a leading dense
layer) are driven through the harness with its look for a chip skipped.
The sound program passes; the control (the plain reference one precision
step lower, int4 weights) reads above the limit; and each fault a serving
cell can have, planted in the program's decode step, its MoE layer or its
leading dense layer, makes ``correct`` false.
"""

import hashlib
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, spec

CHIP = Path(__file__).resolve().parent
SEED = 2 ** 33 + 5

MODEL = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
         "vocab_size": 512, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
         "tie_word_embeddings": False, "hidden_act": "silu"}
# readings at this size (widest logit gap over a sample of ~400 served
# tokens, seeds SEED to SEED + 11): program 0.0053-0.0258, control
# 0.0427-0.0952; the limit sits between, with more room above the program
LIMIT = 0.035
RUNTIME = {"quant": {"mode": "int8_spoga"},
           "kv": {"mode": "paged", "dtype": "int8", "cache_len": 152},
           "scheduler": {"n_slots": 4, "prefill_buckets": [16, 32, 64, 128]}}
CONFIG = {"family": "gqa_dense", "model": MODEL, "check": {"max_logit_gap": LIMIT},
          "program": {"arch": "mistral-nemo-12b", "runtime": RUNTIME}}
MOE_MODEL = dict(MODEL, num_local_experts=4, num_experts_per_tok=2, intermediate_size=256)
# readings of the MoE at this size (capacity factor E / k, dropless; seeds
# SEED to SEED + 11, ~410 served tokens): program 0.0-0.0256, control
# 0.0319-0.127; the limit sits between
MOE_LIMIT = 0.029
MOE_CONFIG = {"family": "gqa_moe", "model": MOE_MODEL, "check": {"max_logit_gap": MOE_LIMIT},
              "program": {"arch": "granite-moe-3b-a800m", "moe_capacity_factor": 2.0,
                          "runtime": RUNTIME}}
# the MoE behind one leading dense layer of its own width (DeepSeekMoE's
# ``first_k_dense_replace``), which the program keeps apart from its scan;
# readings at this size (seeds SEED to SEED + 11, ~410 served tokens):
# program 0.0089-0.0256, control 0.0278-0.098; the limit sits between
LEAD_MODEL = dict(MOE_MODEL, first_k_dense_replace=1, dense_intermediate_size=384)
LEAD_LIMIT = 0.027
LEAD_CONFIG = dict(MOE_CONFIG, model=LEAD_MODEL, check={"max_logit_gap": LEAD_LIMIT})
MIX = {"kind": "open_loop", "rate_rps": 40.0, "stream": True,
       "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.9, "min": 8, "max": 120},
       "output": {"dist": "lognormal", "median": 12, "sigma": 0.8, "min": 4, "max": 24},
       "check_tokens": 400}
WINDOW_S = 1.5


@pytest.fixture(scope="module")
def run_mod():
    spec_ = importlib.util.spec_from_file_location("chipbench_run", CHIP / "run.py")
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


@pytest.fixture
def bench(monkeypatch):
    peaks = spec.peaks_for("TPU v5 lite")
    monkeypatch.setattr(spec, "peaks_for", lambda kind: peaks)
    return json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())


@pytest.fixture
def cell(bench):
    return spec.Cell("nemo12b-chat", "tiny", "tiny", 1, CONFIG, MIX, bench)


@pytest.fixture
def moe_cell(bench):
    return spec.Cell("granite3b-chat", "tiny-moe", "tiny", 1, MOE_CONFIG, MIX, bench)


@pytest.fixture
def lead_cell(bench):
    return spec.Cell("granite3b-chat", "tiny-lead", "tiny", 1, LEAD_CONFIG, MIX, bench)


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


def test_sound_program_is_correct_and_control_is_not(run_mod, cell):
    res = run_mod.run_cell(cell, SEED, WINDOW_S, False, jax.devices())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}

    # the control: the reference at int4 weights, on the tokens served
    _, engine, (tracked, _, _, _) = run_mod.serve(cell, SEED, WINDOW_S)
    pairs = run_mod.check_pairs(cell, engine, tracked, SEED)
    g = check.gaps("gqa_dense", MODEL, SEED, pairs, quant_ctrl="w4a8")
    assert g["served"].max() <= LIMIT < g["control"].max()
    assert not check.verdict({"max_logit_gap": {"value": float(g["control"].max()),
                                                "limit": LIMIT}})


def test_sound_moe_is_correct_and_control_is_not(run_mod, moe_cell):
    res = run_mod.run_cell(moe_cell, SEED, WINDOW_S, False, jax.devices())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    _, engine, (tracked, _, _, _) = run_mod.serve(moe_cell, SEED, WINDOW_S)
    pairs = run_mod.check_pairs(moe_cell, engine, tracked, SEED)
    g = check.gaps("gqa_moe", MOE_MODEL, SEED, pairs, quant_ctrl="w4a8")
    assert g["served"].max() <= MOE_LIMIT < g["control"].max()


def test_sound_moe_behind_a_dense_layer_is_correct_and_control_is_not(run_mod, lead_cell):
    """A stack whose layers differ: the family's ``layer_kinds`` puts a
    dense layer first; the program keeps it in ``head_blocks``."""
    res = run_mod.run_cell(lead_cell, SEED, WINDOW_S, False, jax.devices())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    _, engine, (tracked, _, _, _) = run_mod.serve(lead_cell, SEED, WINDOW_S)
    assert len(engine.params["head_blocks"]) == 1
    pairs = run_mod.check_pairs(lead_cell, engine, tracked, SEED)
    g = check.gaps("gqa_moe", LEAD_MODEL, SEED, pairs, quant_ctrl="w4a8")
    assert g["served"].max() <= LEAD_LIMIT < g["control"].max()


def _dense_layer_fault():
    """The program's decode with the leading dense layer's MLP left out
    (its down projection zero); every other layer sound."""
    from repro.models import transformer as tfm

    sound = tfm.apply_block_decode

    def planted(x_t, p, kind, *args, **kw):
        if kind == "dense_ffn_layer":
            p = dict(p, mlp=dict(p["mlp"], w_down=jnp.zeros_like(p["mlp"]["w_down"])))
        return sound(x_t, p, kind, *args, **kw)

    return planted


def test_planted_fault_in_the_dense_layer_is_not_correct(run_mod, lead_cell, fresh_programs,
                                                         monkeypatch):
    from repro.models import transformer as tfm

    monkeypatch.setattr(tfm, "apply_block_decode", _dense_layer_fault())
    res = run_mod.run_cell(lead_cell, SEED + 1, WINDOW_S, False, jax.devices())
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > LEAD_LIMIT


def test_backlog_cell_runs_through_chunked_prefill_and_is_correct(run_mod, bench):
    """The offline-batch drive loop: a backlog fed so that a lane that frees
    is refilled at once, prompts longer than a chunk."""
    mix = {"kind": "backlog", "backlog": 60, "stream": False,
           "prompt": {"dist": "loguniform", "min": 64, "max": 200},
           "output": {"dist": "uniform", "min": 4, "max": 12},
           "check_tokens": 100}
    runtime = dict(RUNTIME, kv=dict(RUNTIME["kv"], cache_len=224),
                   scheduler={"n_slots": 4, "prefill_chunk": 32})
    config = dict(CONFIG, program=dict(CONFIG["program"], runtime=runtime))
    backlog = spec.Cell("nemo12b-chat", "tiny", "tiny", 1, config, mix, bench)
    res = run_mod.run_cell(backlog, SEED + 2, WINDOW_S, False, jax.devices())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_a_fill_serves_before_the_window_and_counts_as_set_up(run_mod, cell):
    """A mix with a fill: the window opens on an engine already serving at
    the mix's rate; what the fill served is not measured."""
    mix = dict(MIX, fill_s=1.0)
    filled = spec.Cell("nemo12b-chat", "tiny", "tiny", 1, CONFIG, mix, cell.bench)
    _, _, (tracked, window, _, _) = run_mod.serve(filled, SEED + 3, WINDOW_S)
    early = [t for t in tracked if t.due < window[0]]
    assert len(early) == 40 and all(t.req.output_tokens for t in early)
    assert sum(1 for t in tracked if window[0] <= t.due < window[1]) == 60
    assert window[0] - early[0].due == pytest.approx(1.0)
    assert window[0] - run_mod.T_START >= 1.0       # set-up holds the fill


def _decode_fault(kind):
    from repro.serving import engine as eng

    sound = eng.make_serve_step

    def planted(cfg, with_active=False):
        decode = sound(cfg, with_active=with_active)

        def step(params, tokens, cache, active=None):
            if kind == "half_batch":
                active = active & (jnp.arange(active.shape[0]) % 2 == 0)
            logits, new_cache = decode(params, tokens, cache, active)
            if kind == "token_altered":
                logits = jnp.roll(logits, 1, axis=-1)
            if kind == "state_unchanged":
                new_cache = cache
            return logits, new_cache

        return step

    return planted


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(run_mod, cell, fresh_programs, monkeypatch, fault):
    monkeypatch.setattr(fresh_programs, "make_serve_step", _decode_fault(fault))
    res = run_mod.run_cell(cell, SEED + 1, WINDOW_S, False, jax.devices())
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT


def _moe_fault(kind):
    """The program's MoE layer with one expert's output dropped, or every
    token sent to the wrong experts (the router's columns rolled by one)."""
    from repro.models import moe

    sound = moe.moe_ffn

    def planted(x, p, cfg):
        if kind == "expert_dropped":
            p = dict(p, experts_down=p["experts_down"].at[0].set(0))
        if kind == "expert_misrouted":
            p = dict(p, router=jnp.roll(p["router"], 1, axis=-1))
        return sound(x, p, cfg)

    return planted


@pytest.mark.parametrize("fault", ["expert_dropped", "expert_misrouted"])
def test_planted_moe_fault_is_not_correct(run_mod, moe_cell, fresh_programs, monkeypatch,
                                          fault):
    from repro.models import moe

    monkeypatch.setattr(moe, "moe_ffn", _moe_fault(fault))
    res = run_mod.run_cell(moe_cell, SEED + 1, WINDOW_S, False, jax.devices())
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > MOE_LIMIT


def _program_layer(params, layer):
    """Layer ``layer``'s subtree of the program's parameter tree:
    ``head_blocks``, then the stacked slots period by period, then
    ``tail_blocks``."""
    head = params.get("head_blocks", [])
    if layer < len(head):
        return head[layer]
    slots = params["blocks"]
    periods = jax.tree_util.tree_leaves(slots[0])[0].shape[0] if slots else 0
    q, s = divmod(layer - len(head), len(slots))
    if q < periods:
        return jax.tree_util.tree_map(lambda x: x[q], slots[s])
    return params["tail_blocks"][layer - len(head) - periods * len(slots)]


@pytest.mark.parametrize("conf, kinds", [
    (CONFIG, [None] * 3), (MOE_CONFIG, ["moe"] * 3), (LEAD_CONFIG, ["dense", "moe", "moe"])],
    ids=["dense", "moe", "lead"])
def test_reference_draws_the_weights_the_program_was_given(conf, kinds, bench):
    """The reference never reads the program's arrays; it draws them
    again from the seed, layer by layer with its kind's specs, and gets
    the same values (in the published head order, which the program's
    ``wq``/``wo`` permute), wherever the program keeps the layer."""
    from chipbench import weights as W
    from reference import layer_kinds
    from repro.models.model import param_shapes

    conf = dict(conf)
    m = conf["model"] = dict(conf["model"], num_hidden_layers=3)
    c = spec.Cell("x", "x", "x", 1, conf, MIX, bench)
    params = c.program_params(SEED, param_shapes(spec.model_config(c)))
    root = W.root_key(W.seed_words(SEED))
    hq, hkv, hd, d = 4, 2, 32, 128
    assert layer_kinds(c.reference, m) == kinds
    for layer, kind in enumerate(kinds):
        ref = W.layer_weights(root, c.reference.layer_specs(m, kind), layer)
        blk = _program_layer(params, layer)
        wq = blk["attn"]["wq"].reshape(d, hq // hkv, hkv, hd).transpose(0, 2, 1, 3)
        assert jnp.array_equal(wq.reshape(d, hq * hd), ref["attn/wq"])
        wo = blk["attn"]["wo"].reshape(hq // hkv, hkv, hd, d).transpose(1, 0, 2, 3)
        assert jnp.array_equal(wo.reshape(hq * hd, d), ref["attn/wo"])
        for name, leaf in ref.items():
            if name not in ("attn/wq", "attn/wo"):
                group, key = name.split("/") if "/" in name else (None, name)
                got = blk[group][key] if group else blk[key]
                assert jnp.array_equal(got, leaf), name
        assert set(blk) == {name.split("/")[0] for name in ref}
    for name in ("embed", "head"):
        table = jax.jit(lambda: W.table(root, m, name))()
        assert jnp.array_equal(params[name], table)


# sha256 of the program's parameter tree (each leaf's path and bytes) for
# CONFIG and MOE_CONFIG at SEED, drawn before stacks of several layer kinds
# were taken: the uniform stacks still get every bit they got
PARAMS_SHA256 = {
    "dense": "8efee323a56b4d598ef28bdf2d70bcf43fe19075950c64afb3ae83b813a48c92",
    "moe": "12f5423ffc4cfa47306740e11a27848dad0fe9f6e6c73f8f3e454fc1125aaace"}


def _uniform_rows(fam, m, seed, pairs, quant):
    """The reference's rows as a stack of one kind ran them: one layer
    program and one set of specs for every layer."""
    from chipbench import weights as W
    from reference import decoder

    f = decoder.family(fam)
    layer_fn = jax.jit(lambda x, w: f.layer(x, w, m, quant))
    specs = f.layer_specs(m)
    words = W.seed_words(seed)
    embed = jax.jit(lambda kw: W.table(W.root_key(kw), m, "embed"))(words)
    xs = []
    for p, s in pairs:
        toks = list(p) + list(s[:-1])
        ids = np.zeros((decoder._padded(len(toks)),), np.int32)
        ids[:len(toks)] = toks
        xs.append(decoder._embed_rows(embed, ids))
    layer_w = jax.jit(lambda kw, l: W.layer_weights(W.root_key(kw), specs, l))
    for layer in range(m["num_hidden_layers"]):
        w = layer_w(words, layer)
        xs = [layer_fn(x, w) for x in xs]
    g = jax.jit(lambda kw: W.final_norm(W.root_key(kw), m))(words)
    rows = []
    for x, (p, s) in zip(xs, pairs):
        n = len(s)
        idx = np.zeros((decoder._pad_to(n, decoder.ROW_PAD),), np.int32)
        idx[:n] = np.arange(len(p) - 1, len(p) - 1 + n)
        rows.append(np.asarray(decoder._final_rows(x, g, idx, float(m["rms_norm_eps"])))[:n])
    return np.concatenate(rows)


@pytest.mark.parametrize("conf", [CONFIG, MOE_CONFIG], ids=["dense", "moe"])
def test_uniform_stacks_draw_and_run_as_before(conf, bench):
    """Layer kinds change nothing for a family of one kind: the program's
    parameters are bit-identical to those recorded before, and the
    reference's rows (sound and control) to one layer program run over
    every layer."""
    from reference import decoder
    from repro.models.model import param_shapes

    c = spec.Cell("x", "x", "x", 1, conf, MIX, bench)
    params = c.program_params(SEED, param_shapes(spec.model_config(c)))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PARAMS_SHA256[c.family.split("_")[1]]
    rng = np.random.default_rng(3)
    pairs = [(rng.integers(1, 512, n).tolist(), rng.integers(1, 512, k).tolist())
             for n, k in ((40, 12), (9, 5), (100, 20))]
    for quant in (None, "w4a8"):
        rows = decoder.scored_rows(c.family, c.model, SEED, pairs, quant=quant)
        assert np.array_equal(rows, _uniform_rows(c.family, c.model, SEED, pairs, quant))
