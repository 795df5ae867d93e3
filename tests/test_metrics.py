"""Engine telemetry: registry units, lifecycle spans, flight recorder.

Unit level: ``Counter``/``Gauge``/``Histogram`` windowed semantics
(cumulative totals survive a window reset, ``le``-inclusive bucket
edges exactly like Prometheus), registry kind validation, text
exposition format, and the flight-recorder ring + Chrome trace
rendering on synthetic records.  System level: the whole engine runs on
an injectable clock (a frozen clock yields exactly-zero durations
everywhere -- the regression test for stray ``time.perf_counter()``
calls); engine-native TTFT/TPOT from the lifecycle tracer agree
*exactly* with bench-side arithmetic under a manually stepped clock;
spans close under preemption/swap/abort/quarantine (zero open spans
after drain); a quarantine and a forced ``EngineError`` both dump the
flight recorder (the error carries it as ``.flight``) and the dump
renders as valid Chrome ``trace_event`` JSON; and telemetry is
trace-neutral: metrics on vs off changes neither trace counts nor
tokens.
"""
import json

import jax
import numpy as np
import pytest

from repro.config import ParallelConfig, ServeConfig, get_model_config, \
    reduce_for_smoke
from repro.serving.core import EngineCore
from repro.serving.faults import EngineError, FaultInjector
from repro.serving.metrics import (DEFAULT_TIME_BUCKETS, Counter,
                                   FlightRecorder, Gauge, Histogram,
                                   MetricsRegistry)
from repro.serving.scheduler import SamplingParams


# ---------------------------------------------------------------------------
# unit: the registry primitives
# ---------------------------------------------------------------------------

def test_counter_window_vs_cumulative():
    c = Counter("x_total")
    c.inc()
    c.inc(4)
    assert c.value == 5 and c.window == 5
    c.reset_window()
    assert c.value == 5 and c.window == 0   # total is Prometheus-monotonic
    c.inc(2)
    assert c.value == 7 and c.window == 2
    assert c.snapshot() == {"type": "counter", "total": 7, "window": 2}


def test_gauge_last_value_vs_high_water():
    g = Gauge("pages")
    g.set(5)
    g.set(3)
    assert g.value == 3                      # plain gauge: last write wins
    hw = Gauge("peak", high_water=True)
    hw.set(5)
    hw.set(3)
    assert hw.value == 5                     # high water: window max
    hw.reset_window()
    assert hw.value == 0.0                   # re-arms
    g.reset_window()
    assert g.value == 3                      # plain gauge untouched


def test_histogram_bucket_edges_are_le_inclusive():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
    h.observe(1.0)            # == edge: lands in that edge's bucket
    h.observe(1.0 + 1e-9)     # just above: next bucket
    h.observe(4.0)
    h.observe(9.0)            # above the last edge: +Inf only
    snap = h.snapshot()
    assert snap["buckets"] == {1.0: 1, 2.0: 2, 4.0: 3}   # cumulative
    assert snap["count"] == 4 and snap["max"] == 9.0 and snap["min"] == 1.0
    assert h.total_count == 4
    # bucketed percentiles: smallest edge covering the quantile
    assert h.percentile(25) == 1.0
    assert h.percentile(75) == 4.0
    assert h.percentile(100) == 9.0          # window max beyond the edges
    h.reset_window()
    assert h.count == 0 and h.percentile(50) == 0.0
    assert h.total_count == 4                # cumulative survives
    with pytest.raises(ValueError, match="bucket"):
        Histogram("empty", buckets=())


def test_registry_get_or_create_and_kind_mismatch():
    r = MetricsRegistry()
    c = r.counter("a_total")
    assert r.counter("a_total") is c         # get-or-create: same object
    with pytest.raises(TypeError, match="a_total"):
        r.gauge("a_total")
    with pytest.raises(TypeError, match="Histogram"):
        r.histogram("a_total")
    r.observe("h", 0.5)
    assert "h" in r and isinstance(r["h"], Histogram)
    assert r.names() == ["a_total", "h"]


def test_registry_snapshot_reset_partitions_time():
    r = MetricsRegistry()
    r.inc("n_total", 3)
    r.observe("h", 0.2)
    first = r.snapshot(reset=True)           # atomically opens window 2
    assert first["n_total"]["window"] == 3
    assert first["h"]["count"] == 1
    r.inc("n_total", 2)
    second = r.snapshot()
    assert second["n_total"] == {"type": "counter", "total": 5, "window": 2}
    assert second["h"]["count"] == 0         # window 2 saw no observations
    assert json.loads(json.dumps(r.to_json()))  # JSON-safe by construction


def test_prometheus_exposition_format():
    r = MetricsRegistry()
    r.counter("req_total", help="requests").inc(3)
    r.gauge("pages").set(7)
    h = r.histogram("lat_seconds", buckets=(0.5, 1.0))
    h.observe(0.5)
    h.observe(2.0)
    r.reset_window()                          # totals must keep exposing
    h.observe(0.25)
    text = r.to_prometheus()
    lines = text.splitlines()
    assert "# HELP req_total requests" in lines
    assert "# TYPE req_total counter" in lines
    assert "req_total 3" in lines
    assert "# TYPE pages gauge" in lines and "pages 7" in lines
    assert "# TYPE lat_seconds histogram" in lines
    # windowed bucket counts, but +Inf/_sum/_count from the cumulative
    # track: a scrape after a window reset must stay monotonic
    assert 'lat_seconds_bucket{le="0.5"} 1' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "lat_seconds_count 3" in lines
    assert "lat_seconds_sum 2.75" in lines
    assert text.endswith("\n")


def test_flight_recorder_ring_and_chrome_trace():
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(0)
    fr = FlightRecorder(capacity=2)
    for i in range(3):
        fr.record({"step": i, "t_start": float(i), "dur_s": 0.5,
                   "phases": {"schedule": 0.1, "decode": 0.4},
                   "events": 2, "pages_used": 4, "quarantined": [],
                   "faults_fired": 0})
    assert [r["step"] for r in fr.records] == [1, 2]   # ring dropped step 0
    dump = fr.dump()
    assert fr.dumps == 1 and len(dump) == 2
    dump[-1]["quarantined"] = [{"request_id": 7, "code": "failed",
                                "detail": "boom"}]
    dump[-1]["error"] = "EngineError: boom"
    trace = fr.to_chrome_trace(dump)
    assert fr.dumps == 1                      # rendering is not a dump
    evs = trace["traceEvents"]
    steps = [e for e in evs if e["cat"] == "step"]
    phases = [e for e in evs if e["cat"] == "phase"]
    faults = [e for e in evs if e["cat"] == "fault"]
    assert [e["ph"] for e in steps] == ["X", "X"]
    assert steps[0]["ts"] == 1.0 * 1e6 and steps[0]["dur"] == 0.5 * 1e6
    assert steps[0]["args"]["pages_used"] == 4
    # phase durations exact, laid out sequentially within the step
    assert phases[0]["ts"] == steps[0]["ts"]
    assert phases[1]["ts"] == phases[0]["ts"] + phases[0]["dur"]
    assert {e["name"] for e in faults} == {"quarantine", "engine-error"}
    assert all(e["ph"] == "i" for e in faults)
    json.dumps(trace)                         # must serialise as-is


# ---------------------------------------------------------------------------
# system fixtures (the same smoke engine the fault suite drives)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def built():
    from repro.models import build_model
    cfg = reduce_for_smoke(get_model_config("gemma2-2b"))
    model = build_model(cfg, ParallelConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


def _core(built, *, injector=None, clock=None, **serve_kw):
    model, params, cfg = built
    serve_kw.setdefault("max_batch", 3)
    serve_kw.setdefault("max_seq_len", 96)
    serve_kw.setdefault("page_size", 16)
    serve_kw.setdefault("prefill_chunk", 16)
    serve_kw.setdefault("debug_invariants", True)
    return EngineCore(model, params, cfg, ServeConfig(**serve_kw),
                      injector=injector, clock=clock), cfg


def _drain(core, toks=None, max_steps=2000):
    toks = {} if toks is None else toks
    steps = 0
    while core.has_work:
        steps += 1
        assert steps <= max_steps, "engine failed to drain"
        for ev in core.step():
            if ev.kind == "token":
                toks.setdefault(ev.request_id, []).append(ev.token)
    return toks


class ManualClock:
    """Deterministic engine clock the test advances explicitly."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# system: every engine timing flows through the injectable clock
# ---------------------------------------------------------------------------

def test_frozen_clock_zeroes_every_engine_duration(built):
    """Regression for stray wall-clock reads: with the injected clock
    frozen, every duration the engine reports -- step time, phase
    breakdown, TTFT, TPOT, queue delay, e2e -- must be *exactly* 0.0
    even though real wall time passes.  Any code path still calling
    time.perf_counter()/time.monotonic() directly would mix real
    timestamps into the arithmetic and blow these sums up."""
    core, cfg = _core(built, clock=lambda: 1000.0, prefix_cache=True)
    rng = np.random.default_rng(3)
    for i in range(4):
        core.add_request(rng.integers(0, cfg.vocab_size, size=5 + 7 * i),
                         SamplingParams(max_new_tokens=4), request_id=i)
    toks = _drain(core)
    assert len(toks) == 4
    # a page-aligned prompt served twice: the second hits the prefix
    # cache in full and copies its shared tail page (cow_replay)
    prompt = rng.integers(0, cfg.vocab_size, size=32)
    for i in (4, 5):
        core.add_request(prompt, SamplingParams(max_new_tokens=2),
                         request_id=i)
        _drain(core, toks)
    assert len(toks) == 6
    assert core.step_s_high_water == 0.0
    m = core.metrics
    h_step = m["engine_step_seconds"]
    assert h_step.count == core.steps > 0 and h_step.sum == 0.0
    phases = {name[len("engine_phase_"):-len("_seconds")]
              for name in m.names() if name.startswith("engine_phase_")}
    assert phases == {"schedule", "swap", "grow", "cow_replay", "prefill",
                      "decode", "guard", "sample", "stream"}
    for phase in phases:
        h = m[f"engine_phase_{phase}_seconds"]
        assert h.count > 0 and h.sum == 0.0, \
            f"{phase} saw a non-clock duration"
    for rec in core.tracer.completed:
        assert rec["first_token_t"] == rec["submit_t"] == rec["end_t"]
    assert m["engine_ttft_seconds"].count == 6
    assert m["engine_ttft_seconds"].sum == 0.0
    assert m["engine_e2e_seconds"].sum == 0.0
    # flight records carry the frozen timeline too
    assert all(r["dur_s"] == 0.0 for r in core.flight.records)


def test_engine_native_latency_matches_bench_arithmetic(built):
    """The acceptance check: TTFT/TPOT computed by the engine's
    lifecycle tracer equal a bench driver's own arithmetic *exactly*.
    The manual clock only moves between steps, so the in-step stamp the
    tracer takes and the post-step stamp the driver takes read the same
    value -- any disagreement is a bookkeeping bug, not timing noise."""
    clock = ManualClock()
    core, cfg = _core(built, clock=clock, max_batch=2)
    rng = np.random.default_rng(11)
    specs = {i: (rng.integers(0, cfg.vocab_size, size=4 + 9 * i), 3 + i)
             for i in range(4)}
    arrivals = {0: 0, 1: 0, 2: 2, 3: 5}      # 4 requests onto 2 slots:
    t_arrive, t_first, t_last, n_toks = {}, {}, {}, {}  # real queueing
    step_idx, pending = 0, sorted(specs)
    while pending or core.has_work:
        for rid in [r for r in pending if arrivals[r] <= step_idx]:
            prompt, n = specs[rid]
            core.add_request(prompt, SamplingParams(max_new_tokens=n),
                             request_id=rid)
            t_arrive[rid] = clock()
            pending.remove(rid)
        clock.advance(1.0)                   # the step "takes" 1s
        for ev in core.step():
            t_first.setdefault(ev.request_id, clock())
            t_last[ev.request_id] = clock()
            n_toks[ev.request_id] = n_toks.get(ev.request_id, 0) + 1
        step_idx += 1

    recs = {r["id"]: r for r in core.tracer.completed}
    assert sorted(recs) == sorted(specs)
    for rid in specs:
        rec = recs[rid]
        assert rec["reason"] == "finished"
        assert rec["n_tokens"] == n_toks[rid] == specs[rid][1]
        # exact equality -- no tolerance
        assert rec["first_token_t"] - rec["submit_t"] \
            == t_first[rid] - t_arrive[rid]
        if n_toks[rid] > 1:
            assert rec["tpot_s"] == (t_last[rid] - t_first[rid]) \
                / (n_toks[rid] - 1)
    # the histograms saw the same populations
    m = core.metrics
    assert m["engine_ttft_seconds"].count == len(specs)
    assert m["engine_tpot_seconds"].count == \
        sum(1 for r in specs if specs[r][1] > 1)
    # requests 2 and 3 arrived while both slots were busy: their queue
    # delay (submit -> first admission) must be visible and positive
    assert m["engine_queue_delay_seconds"].count == len(specs)
    assert m["engine_queue_delay_seconds"].window_max > 0.0
    assert core.tracer.open_span_count() == 0


# ---------------------------------------------------------------------------
# system: span lifecycle under preemption / swap / abort / quarantine
# ---------------------------------------------------------------------------

def test_spans_close_under_preemption_swap_and_abort(built):
    core, cfg = _core(built, num_pages=10, preempt_policy="swap")
    rng = np.random.default_rng(21)
    for i in range(4):                        # oversubscribed: 4 long
        core.add_request(rng.integers(0, cfg.vocab_size, size=30),
                         SamplingParams(max_new_tokens=30), request_id=i)
    for _ in range(6):
        core.step()
    assert core.abort(3)                      # client disconnect mid-run
    toks = _drain(core)
    stats = core.stats()
    assert stats["pressure"]["preemptions"] > 0, "pool never pressured"
    assert core.tracer.open_span_count() == 0, "leaked lifecycle spans"
    recs = {r["id"]: r for r in core.tracer.completed}
    assert recs[3]["reason"] == "aborted"
    assert all(recs[i]["reason"] == "finished" for i in toks if i != 3)
    m = core.metrics
    # every evict -> re-admit round trip was measured; the abort may
    # have cut request 3's last round trip short (that span closes
    # unobserved at the terminal, which is the point)
    preempts = stats["pressure"]["preemptions"]
    stalls = m["engine_preempt_stall_seconds"].count
    assert preempts - recs[3]["preemptions"] <= stalls <= preempts
    assert stalls > 0
    assert sum(r["preemptions"] for r in recs.values()) == preempts
    assert m["engine_requests_submitted_total"].window == 4
    assert m["engine_requests_finished_total"].window == len(toks)
    # the per-request trace journals the preemption round-trip
    preempted = [r for r in core.sched.finished
                 if any(e.startswith("preempted:") for e, _ in r.trace)]
    assert preempted, "no request journaled its preemption"
    for req in preempted:
        names = [e for e, _ in req.trace]
        if req.id == 3:
            continue                          # aborted before resuming
        assert "resumed" in names[names.index(
            next(e for e in names if e.startswith("preempted:"))):]


def test_quarantine_closes_spans_and_dumps_flight(built):
    inj = FaultInjector(seed=0).arm("sample", nth=(3,))
    core, cfg = _core(built, injector=inj)
    rng = np.random.default_rng(5)
    for i in range(3):
        core.add_request(rng.integers(0, cfg.vocab_size, size=6),
                         SamplingParams(max_new_tokens=4), request_id=i)
    errs = []
    while core.has_work:
        errs += [ev for ev in core.step() if ev.kind == "error"]
    assert len(errs) == 1, "exactly one request should be quarantined"
    victim = errs[0].request_id
    assert core.tracer.open_span_count() == 0
    recs = {r["id"]: r for r in core.tracer.completed}
    assert recs[victim]["reason"] == "failed"
    assert core.stats()["health"]["failed"] == 1
    # the quarantine dumped the flight recorder: the dump's quarantine
    # step names the victim, and it renders as valid Chrome JSON
    dump = core.last_flight_dump
    assert dump, "quarantine must dump the flight recorder"
    q = [e for r in dump for e in r["quarantined"]]
    assert [e["request_id"] for e in q] == [victim]
    assert q[0]["code"] == "failed" and "sample" in q[0]["detail"]
    trace = core.chrome_trace(dump)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "quarantine" in names
    json.dumps(trace)


def test_forced_engine_error_carries_flight_dump(built):
    core, cfg = _core(built, num_pages=10)
    rng = np.random.default_rng(9)
    core.add_request(rng.integers(0, cfg.vocab_size, size=5),
                     SamplingParams(max_new_tokens=3), request_id=0)
    _drain(core)                              # healthy steps fill the ring
    n_healthy = len(core.flight.records)
    assert n_healthy > 0
    core.add_request(rng.integers(0, cfg.vocab_size, size=5),
                     SamplingParams(max_new_tokens=3), request_id=1)
    # force the unreachable-state tripwire: admission yields nothing for
    # a waiting request with no injector to blame
    core.sched.admit = lambda: []
    with pytest.raises(EngineError, match="pool too small") as ei:
        core.step()
    err = ei.value
    assert err.flight and err.flight == core.last_flight_dump
    assert len(err.flight) == n_healthy + 1   # ...plus the fatal step
    last = err.flight[-1]
    assert "pool too small" in last["error"]
    trace = core.chrome_trace(err.flight)
    assert any(e["name"] == "engine-error" for e in trace["traceEvents"])
    json.dumps(trace)


# ---------------------------------------------------------------------------
# system: stats() is a registry view; windows reset; trace-neutrality
# ---------------------------------------------------------------------------

def test_stats_reads_registry_windows_and_reset_reopens(built):
    core, cfg = _core(built)
    rng = np.random.default_rng(7)
    for i in range(3):
        core.add_request(rng.integers(0, cfg.vocab_size, size=4 + i),
                         SamplingParams(max_new_tokens=3), request_id=i)
    _drain(core)
    stats = core.stats()
    m = core.metrics
    assert stats["steps"] == m["engine_steps_total"].window > 0
    assert stats["events_emitted"] == m["engine_events_total"].window == 9
    assert stats["health"]["step_s_high_water"] \
        == m["engine_step_seconds"].window_max > 0.0
    total_before = m["engine_steps_total"].total
    peak_before = core.mgr.peak_used_pages

    core.reset_metrics_window()
    stats = core.stats()
    assert stats["steps"] == 0                # window view restarts...
    assert stats["health"]["step_s_high_water"] == 0.0
    assert core.mgr.peak_used_pages == core.mgr.used_pages == 0
    assert peak_before > 0
    assert not core.tracer.completed and not core.flight.records
    assert m["engine_steps_total"].total == total_before   # ...totals live
    assert f"engine_steps_total {total_before}" in core.export_prometheus()

    # engine.reset() keeps the registry (engine-lifetime, like the jit
    # caches): cumulative counters must survive a state reset
    core.reset()
    assert core.metrics is m
    assert m["engine_steps_total"].total == total_before


def test_telemetry_is_trace_neutral_and_bit_identical(built):
    _, _, cfg = built
    rng = np.random.default_rng(13)
    specs = {i: (rng.integers(0, cfg.vocab_size, size=s), 4)
             for i, s in enumerate((5, 40, 9))}

    def run(metrics_on):
        core, _ = _core(built, metrics=metrics_on, num_pages=13)
        for rid, (p, n) in specs.items():
            core.add_request(p, SamplingParams(max_new_tokens=n),
                             request_id=rid)
        return core, _drain(core)

    on_core, on_toks = run(True)
    off_core, off_toks = run(False)
    assert on_toks == off_toks
    assert on_core.prefill_trace_count == off_core.prefill_trace_count
    assert on_core.prefill_launches == off_core.prefill_launches
    assert on_core.steps == off_core.steps
    assert off_core.tracer is None and off_core.flight is None
    # metrics-off still keeps the stats() contract alive
    assert off_core.stats()["finished"] == len(specs)


def test_serve_engine_wrapper_shares_injectable_clock(built):
    """PR 10 satellite: the dense-path wrapper's measured durations ride
    the same injectable clock as ``EngineCore._clock`` -- one clock
    object governs every timing read in the serving stack."""
    from repro.serving.engine import ServeEngine
    model, params, cfg = built

    class Ticking:
        """Advances a fixed half second per read."""

        def __init__(self):
            self.t = 100.0
            self.reads = 0

        def __call__(self):
            self.reads += 1
            t, self.t = self.t, self.t + 0.5
            return t

    clock = Ticking()
    serve = ServeConfig(max_seq_len=96, page_size=16, prefill_chunk=16,
                        max_batch=2)
    engine = ServeEngine(model=model, params=params, cfg=cfg,
                         serve=serve, clock=clock)
    # the core created by the wrapper reads the *same* clock object
    assert engine.core._clock is engine._clock
    assert engine._clock is clock
    # wrapper-reported throughput is exactly determined by the injected
    # clock: two reads bracket the loop, dt == 0.5s
    before = clock.reads
    rate = engine.throughput_tokens_per_s(batch=2, prompt_len=8,
                                          n_new=4)
    assert clock.reads == before + 2
    assert rate == pytest.approx(2 * 4 / 0.5)


def test_serve_engine_default_clock_is_monotonic(built):
    import time as _time
    from repro.serving.engine import ServeEngine
    model, params, cfg = built
    engine = ServeEngine(model=model, params=params, cfg=cfg)
    assert engine._clock is _time.monotonic
