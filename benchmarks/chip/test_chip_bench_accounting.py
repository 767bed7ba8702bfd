"""The chip benchmark's metric arithmetic on synthetic timestamps, and its
operation and byte counts against hand-worked shapes.  CPU only; nothing
here touches a TPU."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import accounting as acc
from chipbench import spec, traffic
from reference import gqa_dense, gqa_moe

CHIP = Path(__file__).resolve().parent
NEMO = json.loads((CHIP / "configs" / "nemo12b-l10-int8.json").read_text())["model"]
GRANITE = json.loads((CHIP / "configs" / "granite3b-moe-int8.json").read_text())["model"]
PEAKS = json.loads((CHIP / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def _req(due, tokens, prompt_len=10, prefill=None):
    return {"due": due, "prompt_len": prompt_len,
            "token_times": list(tokens), "prefill_events": prefill or []}


def _rec(reqs, window=(100.0, 110.0), end=130.0):
    return {"window": list(window), "end": end, "requests": reqs}


def test_ttft_runs_from_due_time_over_every_request_due_in_window():
    rec = _rec([
        _req(101.0, [101.5, 101.6]),          # 0.5
        _req(102.0, [103.0]),                 # 1.0
        _req(99.0, [99.1]),                   # due before the window: out
        _req(110.0, [110.2]),                 # due at the close: out
        _req(105.0, []),                      # never answered: waits to end
    ])
    vals = sorted(acc.ttft_values(rec))
    assert vals == pytest.approx([0.5, 1.0, 25.0])
    # the missing request sits at the top of the tail
    assert acc.percentile(vals, 90) == pytest.approx(np.percentile(vals, 90))
    assert acc.percentile(vals, 90) > 1.0


def test_itl_takes_every_gap_of_requests_due_in_window():
    rec = _rec([
        _req(101.0, [101.5, 101.6, 101.9]),   # gaps 0.1, 0.3
        _req(109.0, [109.5, 111.0, 111.2]),   # due inside, drains after: 1.5, 0.2
        _req(99.0, [100.5, 100.7]),           # due before: out
    ])
    assert sorted(acc.itl_values(rec)) == pytest.approx([0.1, 0.2, 0.3, 1.5])
    assert acc.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 95) == pytest.approx(4.8)


def test_tok_s_counts_prefilled_rows_and_tokens_inside_window():
    rec = _rec([
        # rows 0..1024 prefilled before the window, 1024..2048 inside
        _req(95.0, [104.0, 105.0, 111.0], prompt_len=2048,
             prefill=[[99.0, 0, 1024], [103.0, 1024, 2048]]),
        _req(106.0, [107.0, 108.0], prompt_len=300, prefill=[[107.0, 0, 300]]),
    ])
    prompt, out = acc.tokens_in_window(rec)
    assert (prompt, out) == (1024 + 300, 4)
    assert acc.tok_s(rec) == pytest.approx((1324 + 4) / 10.0)


def _counts(fam, m):
    return {"model": m, "counts": acc.layer_counts(fam, m)}


def test_layer_parameters_of_nemo_and_granite_by_hand():
    p = gqa_dense.layer_params(NEMO)
    # q/o: 5120 x 4096 each, k/v: 5120 x 1024 each
    assert p["attn"] == 2 * 5120 * 4096 + 2 * 5120 * 1024 == 52_428_800
    assert p["ffn_active"] == 3 * 5120 * 14336 == 220_200_960
    assert p["attn"] + p["ffn_active"] == 272_629_760          # 272.6 M
    assert acc.head_params(NEMO) == 131072 * 5120
    assert acc.body_params(_counts(gqa_dense, NEMO)) == 10 * 272_629_760
    g = gqa_moe.layer_params(GRANITE)
    assert g["attn"] == 2 * 1536 * 1536 + 2 * 1536 * 512
    # the router plus 8 of 40 experts, each gate/up/down of 1536 x 512
    assert g["ffn_active"] == 1536 * 40 + 8 * 3 * 1536 * 512


def test_kernel_gemms_are_the_plain_linears_of_a_layer():
    # nemo: q, k, v, o, then gate, up, down
    assert gqa_dense.kernel_gemms(NEMO) == [
        (5120, 4096), (5120, 1024), (5120, 1024), (4096, 5120),
        (5120, 14336), (5120, 14336), (14336, 5120)]
    # granite: the attention projections; the experts are grouped GEMMs
    assert gqa_moe.kernel_gemms(GRANITE) == [
        (1536, 1536), (1536, 512), (1536, 512), (1536, 1536)]
    step = {"decode": 2, "oneshot_prompts": [600, 64], "chunks": 1}
    serving = {"n_slots": 32, "prefill_buckets": [64, 512, 1024], "prefill_chunk": 256}
    assert acc.gemm_rows(step, serving) == [32, 32, 1024, 64, 256]


@pytest.mark.parametrize("fam, m", [(gqa_dense, NEMO), (gqa_moe, GRANITE)],
                         ids=["nemo", "granite"])
def test_sums_over_a_uniform_stack_are_the_products_of_one_layer(fam, m):
    """Per-layer counts of a stack of one kind add up to one layer's count
    times the depth, as they were taken before."""
    rec = dict(_counts(fam, m), peaks=PEAKS, serving={"n_slots": 32, "prefill_buckets": [64, 4096]},
               steps=[{"t0": 0.0, "t1": 1.0, "decode": 3, "oneshot_prompts": [600], "chunks": 0}])
    n, p = m["num_hidden_layers"], fam.layer_params(m)
    assert acc.body_params(rec) == n * (p["attn"] + p["ffn_active"])
    want = sum(n * sum(acc.gemm_least_time(mm, k, nn, PEAKS) for k, nn in fam.kernel_gemms(m))
               for mm in (32, 32, 32, 4096))
    assert acc.gemm_least_seconds(rec, 0.0, 1.0) == pytest.approx(want, rel=1e-12)
    assert rec["counts"]["attn_dims"] == [m["head_dim"]] * 2
    for first, last in ((0, 600), (600, 601), (17, 4099)):
        keys = (last * (last + 1) - first * (first + 1)) / 2.0
        want = 4.0 * keys * m["num_attention_heads"] * m["head_dim"] * n
        got = acc.attn_ops(m, first, last, rec["counts"]["attn_dims"])
        assert got == pytest.approx(want, rel=1e-12)


def test_counts_follow_the_kind_of_each_layer():
    """A leading dense layer counts its MLP; the MoE layers their router
    and routed experts; the dense layer's MLP GEMMs are plain linears."""
    m = dict(GRANITE, num_hidden_layers=3, first_k_dense_replace=1,
             dense_intermediate_size=4096)
    c = acc.layer_counts(gqa_moe, m)
    attn = gqa_moe.layer_params(GRANITE)["attn"]
    assert [p["ffn_active"] for p in c["layer_params"]] == [
        3 * 1536 * 4096] + [1536 * 40 + 8 * 3 * 1536 * 512] * 2
    assert acc.body_params({"counts": c}) == 3 * attn + 3 * 1536 * 4096 + 2 * (
        1536 * 40 + 8 * 3 * 1536 * 512)
    assert c["kernel_gemms"][0][4:] == [(1536, 4096), (1536, 4096), (4096, 1536)]
    assert c["kernel_gemms"][1] == c["kernel_gemms"][2] == gqa_moe.kernel_gemms(GRANITE)
    # attention whose query-key and value widths differ (MLA: 192 and 128)
    mla = {"num_attention_heads": 16, "head_dim": 128, "num_hidden_layers": 1}
    assert acc.attn_ops(mla, 0, 1, (192, 128)) == 2 * 1 * (192 + 128) * 16


def test_attention_ops_are_causal_sums():
    m = {"num_attention_heads": 2, "head_dim": 4, "num_hidden_layers": 3}
    # queries 0, 1, 2 attend 1 + 2 + 3 keys; 2 products x 2 ops per key,
    # per head dim, per head, per layer
    assert acc.attn_ops(m, 0, 3) == 4 * 6 * 2 * 4 * 3
    assert acc.attn_ops(m, 3, 4) == 4 * 4 * 2 * 4 * 3
    assert acc.attn_ops(m, 0, 4) == acc.attn_ops(m, 0, 3) + acc.attn_ops(m, 3, 4)


def test_model_ops_count_prompt_rows_head_once_per_token_and_decodes():
    m = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 1,
         "vocab_size": 32}
    rec = dict(_rec([_req(100.5, [101.0, 102.0, 103.0], prompt_len=5,
                          prefill=[[101.0, 0, 5]])]), **_counts(gqa_dense, m))
    body = 2 * acc.body_params(rec)
    head = 2 * acc.head_params(m)
    # steps: two inside the window (2.5 s of wall), one straddling its open
    rec["steps"] = [{"t0": 99.5, "t1": 100.5}, {"t0": 100.5, "t1": 102.0},
                    {"t0": 102.0, "t1": 103.0}]
    want = (body * 5 + acc.attn_ops(m, 0, 5)       # prompt rows 0..4
            + head                                  # first token: head only
            + 2 * (head + body)                     # tokens 1 and 2 decoded
            + acc.attn_ops(m, 5, 6) + acc.attn_ops(m, 6, 7))
    assert acc.model_ops_in_window(rec, m) == pytest.approx(want)
    assert acc.step_seconds(rec, 100.0, 110.0) == pytest.approx(2.5)
    assert acc.mfu(rec, m, 1e9) == pytest.approx(100 * want / (2.5 * 1e9))


def test_gemm_least_time_by_hand():
    # decode row block through nemo's gate projection: memory-bound
    nbytes = 8 * 5120 + 5120 * 14336 + 4 * (8 + 14336) + 4 * 8 * 14336
    assert acc.gemm_least_time(8, 5120, 14336, PEAKS) == pytest.approx(nbytes / 819e9)
    # a 1024-row prefill chunk: compute-bound at the int8 peak
    assert acc.gemm_least_time(1024, 5120, 14336, PEAKS) == pytest.approx(
        2 * 1024 * 5120 * 14336 / 393e12)


def test_paged_attention_bytes_by_hand():
    # 2 lanes of 100 and 17 keys: int8 K and V rows plus f32 scales, 8 heads
    want = (100 + 17) * 8 * (2 * 128 + 2 * 4)
    assert acc.paged_attn_bytes(NEMO, [100, 17]) == want
    assert acc.share(1.0, 4.0) == 25.0
    assert acc.share(1.0, 0.0) is None


CHAT = dict(spec.load_mix("chat-stream"), rate_rps=2.0)


def test_traffic_is_a_function_of_the_seed():
    mix = CHAT
    seed = 2 ** 40 + 17
    a = traffic.generate(mix, seed, 30.0, 1000, after_s=20.0)
    b = traffic.generate(mix, seed, 30.0, 1000, after_s=20.0)
    c = traffic.generate(mix, seed + 1, 30.0, 1000, after_s=20.0)
    assert len(a) == len(b) == len(c) == 60 + 40
    assert all(x.due_s == y.due_s and np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert [x.due_s for x in a] != [x.due_s for x in c]
    lens = np.array([len(x.prompt) for x in a])
    assert lens.min() >= mix["prompt"]["min"] and lens.max() <= mix["prompt"]["max"]
    assert all(0 < t < 1000 for x in a for t in x.prompt)


def test_every_seed_gets_the_same_window_of_work_in_another_order():
    mix = CHAT
    runs = [traffic.generate(mix, seed, 30.0, 1000, after_s=20.0)
            for seed in (2 ** 33 + 1, 2 ** 33 + 2)]
    windows = [[r for r in reqs if r.due_s < 30.0] for reqs in runs]
    # exactly rate x window requests fall due in the window, the first at its open
    assert [len(w) for w in windows] == [60, 60]
    assert all(w[0].due_s == 0.0 and max(r.due_s for r in w) < 30.0 for w in windows)
    sizes = [sorted((len(r.prompt), r.max_new_tokens) for r in w) for w in windows]
    assert sorted(p for p, _ in sizes[0]) == sorted(p for p, _ in sizes[1])
    assert sorted(o for _, o in sizes[0]) == sorted(o for _, o in sizes[1])
    # the last gap runs to the close
    gaps = [np.diff([r.due_s for r in w] + [30.0]) for w in windows]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert [len(r.prompt) for r in windows[0]] != [len(r.prompt) for r in windows[1]]
    # the arrivals after the close keep the rate
    assert all(30.0 <= r.due_s < 50.0 for r in runs[0][60:])


def test_stratified_quantiles_follow_the_distribution():
    q = traffic.quantiles({"dist": "lognormal", "median": 600, "sigma": 0.9,
                           "min": 32, "max": 3072}, 41)
    assert q[20] == 600 and list(q) == sorted(q)
    assert q[0] >= 32 and q[-1] == 3072          # the top quantile is clipped
    u = traffic.quantiles({"dist": "uniform", "min": 32, "max": 128}, 97)
    assert u[0] == 32 and u[-1] == 128
    lu = traffic.quantiles({"dist": "loguniform", "min": 4096, "max": 12288}, 3)
    assert list(lu) == [int(4096 * 3 ** (k / 6)) for k in (1, 3, 5)]


def test_a_mix_extends_its_base():
    mix = spec.load_mix("chat-stream-0.76rps")
    base = spec.load_mix("chat-stream")
    assert mix["rate_rps"] == 0.76 and "extends" not in mix
    assert mix["prompt"] == base["prompt"] and mix["output"] == base["output"]
    assert "rate_rps" not in base


def _sizes(reqs):
    """The work of a set of requests, whatever the order: its prompt
    lengths, output lengths and streaming flags, each sorted."""
    return (sorted(len(r.prompt) for r in reqs), sorted(r.max_new_tokens for r in reqs),
            sorted(r.stream for r in reqs))


MIXED = {"kind": "open_loop", "rate_rps": 4.0, "stream": True,
         "output": {"dist": "lognormal", "median": 20, "sigma": 0.8, "min": 4, "max": 64},
         "classes": [
             {"share": 3, "prompt": {"dist": "lognormal", "median": 60, "sigma": 0.9,
                                     "min": 8, "max": 300}},
             {"share": 1, "stream": False,
              "prompt": {"dist": "loguniform", "min": 400, "max": 1200},
              "output": {"dist": "uniform", "min": 4, "max": 8}}]}


def test_classes_share_one_queue_with_their_own_sizes():
    runs = [traffic.generate(MIXED, seed, 10.0, 1000) for seed in (2 ** 35 + 1, 7)]
    for reqs in runs:
        assert len(reqs) == 40
        chat = [r for r in reqs if r.stream]
        batch = [r for r in reqs if not r.stream]
        assert (len(chat), len(batch)) == (30, 10)
        assert all(8 <= len(r.prompt) <= 300 and 4 <= r.max_new_tokens <= 64 for r in chat)
        assert all(400 <= len(r.prompt) <= 1200 and r.max_new_tokens <= 8 for r in batch)
        assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    # the same work for every seed, in another order
    assert _sizes(runs[0]) == _sizes(runs[1])
    assert [r.stream for r in runs[0]] != [r.stream for r in runs[1]]
    assert traffic.max_prompt_len(MIXED) == 1200 and traffic.max_output_len(MIXED) == 64
    assert list(traffic.split([3, 1], 7)) == [5, 2]


def test_bursts_put_every_arrival_in_an_on_phase_at_the_mean_rate():
    mix = dict(CHAT, burst={"period_s": 10.0, "on_s": 2.5})
    reqs = traffic.generate(mix, 2 ** 36 + 3, 40.0, 1000)
    due = np.array([r.due_s for r in reqs])
    assert len(due) == 80 and due[0] == 0.0 and np.all(np.diff(due) >= 0)
    assert np.all(np.mod(due, 10.0) < 2.5) and due.max() < 40.0
    # each of the four periods gets some of the arrivals
    assert sorted(set(np.floor(due / 10.0))) == [0.0, 1.0, 2.0, 3.0]
    plain = traffic.generate(CHAT, 2 ** 36 + 3, 40.0, 1000)
    assert _sizes(reqs) == _sizes(plain)


def test_shared_prefixes_and_repeated_prompts():
    mix = dict(CHAT, prefix={"tokens": 32, "count": 3}, repeat=4)
    reqs = traffic.generate(mix, 2 ** 37 + 9, 30.0, 1000)
    heads = {r.prompt[:32].tobytes() for r in reqs}
    assert len(heads) == 3
    prompts = [r.prompt.tobytes() for r in reqs]
    # 60 requests send 15 distinct prompts, each 4 times
    assert sorted(prompts.count(p) for p in set(prompts)) == [4] * 15
    assert all(len(r.prompt) >= 32 + 32 for r in reqs)
    assert traffic.max_prompt_len(mix) == 3072 + 32
    # the window's sizes are the same for another seed
    other = traffic.generate(mix, 11, 30.0, 1000)
    assert _sizes(reqs) == _sizes(other)


def test_topics_draw_skewed_requests_from_their_own_ids():
    mix = dict(CHAT, topics={"count": 4, "tokens": 50, "zipf": 1.0})
    reqs = traffic.generate(mix, 2 ** 38 + 1, 30.0, 10 ** 6)
    # group the requests by shared ids: every group is one topic's pool
    groups = []          # [ids, requests]
    for r in reqs:
        ids = set(r.prompt.tolist())
        hit = [g for g in groups if g[0] & ids]
        assert len(hit) <= 1
        if hit:
            hit[0][0].update(ids)
            hit[0][1] += 1
        else:
            groups.append([ids, 1])
    assert all(len(ids) <= 50 for ids, _ in groups)
    # shares 1, 1/2, 1/3, 1/4 of 60 requests, by largest remainder
    assert sorted(n for _, n in groups) == [7, 10, 14, 29]
    assert list(traffic.split(1.0 / np.arange(1, 5), 60)) == [29, 14, 10, 7]


def test_a_fill_comes_before_the_window_and_leaves_it_unchanged():
    mix = dict(CHAT, fill_s=10.0)
    seed = 2 ** 39 + 5
    reqs = traffic.generate(mix, seed, 30.0, 1000, after_s=20.0)
    plain = traffic.generate(CHAT, seed, 30.0, 1000, after_s=20.0)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == -10.0
    fill = [r for r in reqs if r.due_s < 0]
    assert len(fill) == 20
    rest = reqs[len(fill):]
    # the window and the arrivals after it are those of the run without a fill
    assert [r.due_s for r in rest] == [r.due_s for r in plain]
    assert [(len(r.prompt), r.max_new_tokens) for r in rest] == [
        (len(r.prompt), r.max_new_tokens) for r in plain]
