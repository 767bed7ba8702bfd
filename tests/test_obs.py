"""The observability layer (``repro.obs``): span tracer + Chrome trace
export, log-bucketed histograms, scheduler event log, the EngineMetrics
facade, and the two engine-level invariants the layer promises — zero
overhead when disabled, bitwise output-invisibility when enabled.
"""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    LLM,
    KVConfig,
    ObsConfig,
    RequestOutput,
    RuntimeConfig,
    SchedulerConfig,
    SpecConfig,
)
from repro.obs import (
    DISABLED,
    EventLog,
    Histogram,
    MetricsRegistry,
    NULL_EVENTS,
    NULL_TRACER,
    StepProfiler,
    Tracer,
)
from repro.api.config import QuantRuntime
from repro.obs.trace import ProfilerSpan
from repro.paging.manager import PageManager
from repro.serving.metrics import EngineMetrics


# ---------------------------------------------------------------------------
# tracer: span nesting, monotonicity, Chrome export
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_monotonic_timestamps():
    tr = Tracer()
    with tr.span("outer", step=1):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b"):
            pass
    # children close (and emit) before the parent
    names = [e["name"] for e in tr.events]
    assert names == ["inner_a", "inner_b", "outer"]
    a, b, outer = tr.events
    assert a["args"]["depth"] == b["args"]["depth"] == 1
    assert outer["args"]["depth"] == 0
    assert outer["args"]["step"] == 1
    # timestamp containment is what Perfetto nests by: the parent span
    # starts before and ends after every child
    assert outer["ts"] <= a["ts"] <= a["ts"] + a["dur"]
    assert a["ts"] + a["dur"] <= b["ts"] + 1e-9 or a["ts"] <= b["ts"]
    assert b["ts"] + b["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    assert all(e["dur"] >= 0 for e in tr.events)


def test_span_set_attaches_args_after_entry():
    tr = Tracer()
    with tr.span("defrag") as sp:
        sp.set(pages_moved=3)
    assert tr.events[-1]["args"]["pages_moved"] == 3


def test_tracer_chrome_document_shape(tmp_path):
    tr = Tracer()
    tr.instant("marker", reason="test")
    with tr.span("work"):
        pass
    doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    # two metadata records lead (process/thread naming), then the events
    assert [e["ph"] for e in evs[:2]] == ["M", "M"]
    assert {e["ph"] for e in evs[2:]} == {"i", "X"}
    assert all(e["pid"] == 1 and e["tid"] == 1 for e in evs)
    # the document is valid JSON and round-trips through save()
    out = tmp_path / "trace.json"
    assert tr.save(str(out)) == str(out)
    assert json.loads(out.read_text())["traceEvents"] == json.loads(
        json.dumps(evs))


def test_null_tracer_is_inert():
    sp1 = NULL_TRACER.span("a", x=1)
    sp2 = NULL_TRACER.span("b")
    # a bare profiler annotation: nothing kept but the name
    assert isinstance(sp1, ProfilerSpan) and not hasattr(sp1, "args")
    with sp1 as sp:
        with sp2:
            sp.set(y=2)
    NULL_TRACER.instant("never")
    assert NULL_TRACER.events == ()
    assert NULL_TRACER.to_chrome()["traceEvents"] == []
    assert NULL_TRACER.save("/nonexistent/should-not-be-written") is None


# ---------------------------------------------------------------------------
# histograms: bucket edges, exact + bucket-interpolated percentiles
# ---------------------------------------------------------------------------

def test_histogram_bucket_edges():
    h = Histogram("lat", base=1e-6, growth=2.0, n_buckets=8)
    # bucket 0 holds everything <= base, including 0 and negatives
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(1e-6) == 0
    # an exact edge is an inclusive UPPER bound of its bucket
    for i in range(1, 7):
        assert h.bucket_index(h.edge(i)) == i
        assert h.bucket_index(h.edge(i) * 1.0001) == i + 1
    # the last bucket is open-ended
    assert h.bucket_index(1e9) == h.n_buckets - 1
    for v in (0.0, 1e-6, 3e-6, 0.5, 1e9):
        h.observe(v)
    assert sum(h.counts) == h.total == 5
    assert h.min == 0.0 and h.max == 1e9


def test_histogram_exact_percentiles_match_numpy():
    h = Histogram("lat")
    rng = np.random.default_rng(0)
    xs = rng.exponential(0.05, size=200)
    for x in xs:
        h.observe(x)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(xs, q))
    assert h.mean == pytest.approx(float(np.mean(xs)))
    # bucket-interpolated estimate lands inside the right bucket
    p95 = h.percentile(95)
    est = h.bucket_percentile(95)
    i = h.bucket_index(p95)
    lo = 0.0 if i == 0 else h.edge(i - 1)
    assert lo <= est <= h.edge(i)


def test_histogram_empty_and_single_sample():
    h = Histogram("lat")
    assert h.percentile(99) == 0.0 and h.bucket_percentile(50) == 0.0
    assert h.mean == 0.0
    h.observe(0.25)
    assert h.percentile(1) == h.percentile(99) == 0.25


def test_registry_creates_on_first_touch_and_snapshots():
    reg = MetricsRegistry()
    reg.inc("steps")
    reg.inc("steps", 2)
    reg.set("pages", 7)
    reg.set_max("peak", 3)
    reg.set_max("peak", 2)  # running max keeps 3
    reg.observe("ttft", 0.5)
    snap = reg.snapshot()
    assert snap["counters"]["steps"] == 3
    assert snap["gauges"] == {"pages": 7, "peak": 3}
    assert snap["histograms"]["ttft"]["count"] == 1
    assert snap["histograms"]["ttft"]["p99"] == 0.5


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

def test_event_log_timeline_and_jsonl(tmp_path):
    log = EventLog()
    log.emit("queued", req_id=1)
    log.emit("queued", req_id=2)
    log.emit("admitted", req_id=1, mode="chunked", queue_wait_s=0.01)
    log.emit("rejected", reason="page_capacity", need_pages=4, available=1)
    log.emit("finished", req_id=1, reason="length")
    assert len(log) == 5
    tl = log.timeline(1)
    assert [e["kind"] for e in tl] == ["queued", "admitted", "finished"]
    assert tl[1]["mode"] == "chunked"
    assert log.kinds() == {"queued": 2, "admitted": 1, "rejected": 1,
                           "finished": 1}
    out = tmp_path / "events.jsonl"
    assert log.to_jsonl(str(out)) == str(out)
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(lines) == 5
    assert all("kind" in ev and "t" in ev for ev in lines)
    # events without a req_id stay out of every timeline
    assert [e["kind"] for e in log.timeline(2)] == ["queued"]


def test_null_event_log_is_inert():
    assert NULL_EVENTS.emit("queued", req_id=1) is None
    assert len(NULL_EVENTS) == 0
    assert NULL_EVENTS.timeline(1) == []
    assert NULL_EVENTS.kinds() == {}
    assert NULL_EVENTS.to_jsonl("/nonexistent/nope") is None


# ---------------------------------------------------------------------------
# ObsConfig resolution + RuntimeConfig round-trip
# ---------------------------------------------------------------------------

def test_obs_config_auto_enable_and_build():
    assert not ObsConfig().resolved_enabled
    assert ObsConfig(trace="t.json").resolved_enabled
    assert ObsConfig(events="e.jsonl").resolved_enabled
    assert ObsConfig(debug_invariants=True).resolved_enabled
    assert ObsConfig(enabled=True).resolved_enabled
    # explicit False wins over sink paths
    assert not ObsConfig(enabled=False, trace="t.json").resolved_enabled
    off = ObsConfig().build()
    assert not off.enabled
    assert off.tracer is NULL_TRACER and off.events is NULL_EVENTS
    assert off.save() == []
    on = ObsConfig(enabled=True).build()
    assert on.enabled and isinstance(on.events, EventLog)
    with pytest.raises(ValueError):
        ObsConfig(profile_steps=0)


def test_runtime_config_obs_roundtrip():
    rc = RuntimeConfig(obs=ObsConfig(trace="t.json", events="e.jsonl",
                                     profile_steps=5,
                                     debug_invariants=True))
    blob = json.dumps(rc.to_dict())
    assert RuntimeConfig.from_dict(json.loads(blob)) == rc
    # obs defaults survive configs serialized before the field existed
    assert RuntimeConfig.from_dict({"max_new_tokens": 4}).obs == ObsConfig()


# ---------------------------------------------------------------------------
# jax.profiler hook
# ---------------------------------------------------------------------------

def test_step_profiler_wraps_n_steps(tmp_path):
    prof = StepProfiler(str(tmp_path), n_steps=1)
    prof.step_begin()
    jnp.ones((4,)).sum().block_until_ready()
    prof.step_end()  # n_steps reached -> trace stopped here
    prof.close()
    prof.close()  # idempotent
    assert any(tmp_path.rglob("*")), "profiler wrote nothing"


def _profiled_engine_spans(obs_cfg, log_dir):
    """A tiny streaming paged engine served for a few steps inside a
    ``jax.profiler`` trace: the ``engine.*`` host events, ``(name, start,
    end)``, and the LLM."""
    from jax.profiler import ProfileData

    runtime = RuntimeConfig(
        reduced=True, quant=QuantRuntime(mode="int8_spoga"),
        kv=KVConfig(mode="paged", page_size=8),
        scheduler=SchedulerConfig(n_slots=2), obs=obs_cfg)
    llm = LLM(arch="llama3.2-1b", runtime=runtime)
    engine = llm.build_engine(12, 6)
    rng = np.random.default_rng(0)

    def add():
        engine.add_request(rng.integers(1, 100, 12).tolist(), 6,
                           on_token=lambda tok: None)

    add()
    while engine.has_work:  # compile outside the trace
        engine.step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        add()
        add()
        for _ in range(3):
            engine.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = log_dir.rglob("*.xplane.pb")
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("engine.")]
    return spans, llm


@pytest.mark.parametrize("obs_cfg", [ObsConfig(), ObsConfig(enabled=True)],
                         ids=["obs_off", "obs_on"])
def test_engine_spans_land_in_a_profiler_trace(obs_cfg, tmp_path):
    spans, llm = _profiled_engine_spans(obs_cfg, tmp_path)
    steps = [(a, b) for name, a, b in spans if name == "engine.step"]
    assert len(steps) == 3
    phases = ("engine.schedule", "engine.page_tables", "engine.decode",
              "engine.token_pull", "engine.token_deliver")
    for a, b in steps:
        inside = {name for name, x, y in spans if a <= x and y <= b}
        assert set(phases) <= inside, sorted(inside)
    # the admission step: its prefill, first-token pull and sync
    names = {name for name, _, _ in spans}
    assert {"engine.prefill", "engine.first_token_pull",
            "engine.admit_sync"} <= names
    # recording, where on, keeps its own names beside the profiler's
    recorded = {e["name"] for e in llm.obs.tracer.events}
    if obs_cfg.resolved_enabled:
        assert {n.removeprefix("engine.") for n in names} <= recorded
    else:
        assert recorded == set()


def _lower_quantized_linear():
    from repro.backends.pipeline import quantized_linear

    x = jnp.ones((4, 64), jnp.bfloat16)
    w = jnp.ones((64, 128), jnp.bfloat16)
    return jax.jit(lambda x, w: quantized_linear(x, w, "int8_spoga")).lower(x, w)


def _lower_grouped_matmul():
    from repro.models.moe import _grouped_matmul

    x = jnp.ones((2, 4, 8, 64), jnp.bfloat16)
    w = jnp.ones((4, 64, 32), jnp.bfloat16)
    return jax.jit(lambda x, w: _grouped_matmul(x, w, "int8_spoga")).lower(x, w)


@pytest.mark.parametrize("lower", [_lower_quantized_linear, _lower_grouped_matmul],
                         ids=["quantized_linear", "grouped_matmul"])
def test_weight_requantization_is_named_in_the_hlo(lower):
    text = lower().as_text(debug_info=True)
    quant = [line for line in text.splitlines() if "weight_quant" in line]
    # the amax reduction and the rounding to int8 codes both carry it
    assert any("reduce" in line and "max" in line for line in quant), quant
    assert any("round" in line for line in quant), quant


# ---------------------------------------------------------------------------
# EngineMetrics facade: empty-run wall clock, deprecation shim
# ---------------------------------------------------------------------------

def test_engine_metrics_empty_run_reports_cleanly():
    m = EngineMetrics()
    assert m.wall_s == 0.0  # never begun -> no phantom wall clock
    r = m.report()
    assert r["requests"] == 0 and r["tokens_per_s"] == 0.0
    assert r["ttft_p99_s"] == 0.0 and r["accept_len_p50"] == 0.0
    m.begin()
    start = m.start_time
    m.begin()  # idempotent: the stamp does not move
    assert m.start_time == start
    m.touch()
    assert 0 < m.wall_s < 10.0
    assert m.end_time >= start


def test_engine_metrics_deprecation_shim():
    m = EngineMetrics()
    with pytest.warns(DeprecationWarning):
        m.prefills = 5
    assert m.prefills == 5  # the poke still lands (compat), just noisily
    m.inc("prefills")
    assert m.prefills == 6
    # reads and the blessed emission API never warn
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        _ = m.prefills
        m.inc("decode_steps")
        m.set_gauge("pages_total", 9)
        m.max_gauge("peak_running", 2)
        m.observe("accept_len", 3)
    assert m.report()["accept_len_p50"] == 3.0
    with pytest.raises(AttributeError):
        _ = m.not_a_metric


# ---------------------------------------------------------------------------
# page-pool invariants: collecting + raising surfaces
# ---------------------------------------------------------------------------

def test_page_manager_invariant_violations_collects_all():
    pm = PageManager(n_pages=8, page_size=4, n_lanes=2, max_pages_per_lane=4)
    assert pm.invariant_violations() == []
    pm.check_invariants()  # healthy pool passes the raising form too
    pm.refcount[1] = 1  # page 1 is still on the free list -> two violations
    bad = pm.invariant_violations()
    assert any("refcount mismatch" in msg for msg in bad)
    assert any("both free and referenced" in msg for msg in bad)
    with pytest.raises(AssertionError):
        pm.check_invariants()


# ---------------------------------------------------------------------------
# engine-level: disabled no-op, timeline completeness, bitwise parity
# ---------------------------------------------------------------------------

def _serve(obs_cfg, tmp_path=None):
    """One paged + chunked + prefix + spec serve (every event source hot)."""
    runtime = RuntimeConfig(
        reduced=True,
        kv=KVConfig(mode="paged", page_size=8, prefix_cache=True),
        scheduler=SchedulerConfig(n_slots=2, prefill_chunk=8),
        spec=SpecConfig(enabled=True, k=2, drafter="ngram"),
        obs=obs_cfg,
    )
    llm = LLM(arch="llama3.2-1b", runtime=runtime)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, llm.config.vocab_size, 16).tolist()
    prompts = [shared + rng.integers(0, llm.config.vocab_size, n).tolist()
               for n in (5, 9, 3)]
    outs = llm.generate(prompts, max_new_tokens=6)
    return llm, outs


def test_disabled_obs_is_noop_and_enabled_is_output_invisible():
    llm_off, outs_off = _serve(ObsConfig())
    # disabled: null sinks saw nothing, outputs carry no timeline
    assert not llm_off.obs.enabled
    assert llm_off.obs.tracer is NULL_TRACER
    assert len(llm_off.obs.events) == 0
    assert all(o.timeline is None and o.queue_wait_s is None
               for o in outs_off)

    # enabled, with the most invasive setting (per-step invariant
    # checking): greedy token streams must stay bitwise equal
    llm_on, outs_on = _serve(ObsConfig(debug_invariants=True))
    assert [o.token_ids for o in outs_on] == [o.token_ids for o in outs_off]

    # spans were recorded for the dispatch kinds this workload exercises
    names = {e["name"] for e in llm_on.obs.tracer.events}
    assert {"step", "chunk"} <= names
    assert names & {"decode", "verify"}
    # every span is a well-formed complete event with monotone bounds
    for e in llm_on.obs.tracer.events:
        assert e["ph"] == "X" and e["dur"] >= 0 and "depth" in e["args"]

    # per-request timelines: queued -> admitted -> ... -> first_token ->
    # finished, in order, with reasons/wait attached
    ids = {o.request_id for o in outs_on}
    for out in outs_on:
        kinds = [e["kind"] for e in out.timeline]
        assert kinds[0] == "queued" and kinds[-1] == "finished"
        assert kinds.index("queued") < kinds.index("admitted")
        assert kinds.index("admitted") < kinds.index("first_token")
        admitted = next(e for e in out.timeline if e["kind"] == "admitted")
        assert admitted["mode"] in ("chunked", "prefix")
        assert admitted["queue_wait_s"] >= 0
        assert out.queue_wait_s == admitted["queue_wait_s"]
        finished = next(e for e in out.timeline if e["kind"] == "finished")
        assert finished["reason"] in ("eos", "length")
        assert all(e["req_id"] in ids for e in out.timeline)
    # the shared prefix makes later requests prefix-admissions, and the
    # 21-token prompts overflow the 8-token chunk -> chunk events exist
    modes = {next(e for e in o.timeline if e["kind"] == "admitted")["mode"]
             for o in outs_on}
    assert "prefix" in modes
    assert any(e["kind"] == "chunk" for o in outs_on for e in o.timeline)

    # the speculative path ran and its metrics carry percentile keys
    rep = llm_on.metrics.report()
    assert rep["verify_dispatches"] >= 1
    assert rep["ttft_p99_s"] >= rep["ttft_p50_s"] >= 0
    assert "accept_len_p99" in rep and "queue_wait_p99_s" in rep


def test_obs_save_writes_configured_sinks(tmp_path):
    trace = tmp_path / "trace.json"
    events = tmp_path / "events.jsonl"
    llm, outs = _serve(ObsConfig(trace=str(trace), events=str(events)))
    assert len(outs) == 3
    written = llm.obs.save()
    assert set(written) == {str(trace), str(events)}
    doc = json.loads(trace.read_text())
    assert doc["traceEvents"] and doc["traceEvents"][0]["ph"] == "M"
    lines = [json.loads(l) for l in events.read_text().splitlines()]
    kinds = {ev["kind"] for ev in lines}
    assert {"queued", "admitted", "first_token", "finished"} <= kinds


def test_request_output_queue_wait_reads_timeline():
    out = RequestOutput(request_id=0, prompt_token_ids=[1], token_ids=[2],
                        text=None, finish_reason="length", ttft_s=0.1,
                        latency_s=0.2,
                        timeline=[{"kind": "queued", "req_id": 0},
                                  {"kind": "admitted", "req_id": 0,
                                   "queue_wait_s": 0.05}])
    assert out.queue_wait_s == 0.05
    assert RequestOutput(request_id=1, prompt_token_ids=[], token_ids=[],
                         text=None, finish_reason="length", ttft_s=None,
                         latency_s=None).queue_wait_s is None
