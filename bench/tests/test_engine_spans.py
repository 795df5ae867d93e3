"""The device-idle split by engine span (``engine_spans``): exact on
synthetic traces, unchanged readings of the recorded phi3 trace, and the
engine's launch spans and counters against the benchmark's own step log
on the CPU."""
import json
import os

import pytest

import engine_spans as es
import trace_reduce as tr
from conftest import CHAT

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"


def _trace(modules, host):
    return tr.Trace({DEV: {"modules": [(s, e, "jit_dec(1)")
                                       for s, e in modules], "ops": []}},
                    host)


def test_split_divides_idle_by_innermost_span():
    # device busy 0-10, 30-40, 70-100; idle 10-30, 40-70
    host = [(0, 100, "window"), (0, 80, "step"), (2, 78, "engine.step"),
            (5, 20, "engine.decode"), (20, 35, "engine.guard"),
            (35, 60, "engine.sample"), (36, 38, "engine.stream"),
            (80, 100, "submit")]
    got = es.split(_trace([(0, 10), (30, 40), (70, 100)], host))
    ns = {k: round(v * 1e9, 6) for k, v in got.items()}
    # 10-30 straddles decode and guard; 40-70 runs from sample through
    # the rest of engine.step (60-70 in no phase), not given to its
    # midpoint's span
    assert ns == {"engine.decode": 10.0, "engine.guard": 10.0,
                  "engine.sample": 20.0, "engine.step": 10.0,
                  **{n: 0.0 for n in es.ENGINE_SPANS
                     if n not in ("engine.decode", "engine.guard",
                                  "engine.sample", "engine.step")},
                  es.STEP: 0.0, es.OUTSIDE: 0.0}
    r = tr.reduce_trace(_trace([(0, 10), (30, 40), (70, 100)], host))
    assert sum(got.values()) == pytest.approx(r.window_s - r.busy_mean_s)
    assert es.idle_pct(got, r.window_s, es.SAMPLE) == pytest.approx(30.0)
    assert es.idle_pct(got, r.window_s, es.LAUNCH) == pytest.approx(10.0)


def test_split_outside_engine_spans_and_without_them():
    # a gap from a step's end into the time between steps, and one in a
    # step before the engine's span opens
    host = [(0, 50, "step"), (10, 45, "engine.step"),
            (12, 40, "engine.decode"), (50, 60, "wait"),
            (60, 100, "step"), (70, 95, "engine.step")]
    got = es.split(_trace([(0, 2), (12, 44), (80, 100)], host))
    ns = {k: round(v * 1e9, 6) for k, v in got.items() if v}
    assert ns == {es.STEP: 8.0 + 5.0 + 10.0,
                  "engine.step": 2.0 + 1.0 + 10.0, es.OUTSIDE: 10.0}
    # a program without engine spans: no engine key, nothing to read
    old = es.split(_trace([(0, 2), (12, 44), (80, 100)],
                          [h for h in host if h[2] in tr.HOST_SPANS]))
    assert set(old) == {es.STEP, es.OUTSIDE}
    assert es.idle_pct(old, 1e-7, es.SAMPLE) is None
    assert es.split(tr.Trace({}, host)) is None


def test_recorded_phi3_trace_reads_as_before():
    """Every reading of the recorded trace of a program without engine
    spans, bit for bit as before the split existed."""
    with open(os.path.join(DATA, "trace_v5e_phi3_3steps.json")) as f:
        t = tr.Trace.from_dict(json.load(f))
    r = tr.reduce_trace(t, ["paged_flash_decode_fwd", "paged_prefill_fwd"])
    assert (r.window_s, r.busy_mean_s, r.idle_share, r.step_s, r.steps) \
        == (0.263544823, 0.251440839, 0.045927610575753786, 0.263520213, 3)
    assert r.kernel_s == {"paged_flash_decode_fwd": 0.09769661600000003}
    assert r.idle_by_host == {"step": 0.012103984}
    assert 100.0 * r.idle_share == 4.592761057575379
    got = es.split(t)
    assert got == {es.STEP: 0.012079374, es.OUTSIDE: 2.461e-05}
    assert sum(got.values()) == pytest.approx(r.window_s - r.busy_mean_s,
                                              rel=1e-12)


def _run_for(metric_names, trace):
    """Each reader's value on a fixed step log over the reduced trace."""
    import run
    from conftest import ROOT
    from manifest import Manifest
    man = Manifest(ROOT)
    cfg = man.config({"config": "phi3-mini-3.8b"})
    launches = [{"kind": "decode", "t": 0.5 + i,
                 "ctx": [700 + 37 * j + i for j in range(8)]}
                for i in range(3)]
    r = run.Run(cfg=cfg, mix={}, cell={"name": "A"},
                peaks=man.peaks("TPU v5 lite"), chips=1, t0=0.0, t1=10.0,
                recs={}, setup_s=1.0, counters={}, usable_pages=79,
                launches=launches, trace=trace, trace_t0=0.0, trace_t1=10.0,
                decode_kernel="paged_flash_decode_fwd",
                prefill_kernel="paged_prefill_fwd")
    return {n: man.reader(n)(r) for n in metric_names}


def test_recorded_phi3_trace_metrics_read_as_before():
    with open(os.path.join(DATA, "trace_v5e_phi3_3steps.json")) as f:
        t = tr.Trace.from_dict(json.load(f))
    r = tr.reduce_trace(t, ["paged_flash_decode_fwd", "paged_prefill_fwd"])
    assert _run_for(["device_idle_pct", "step_mfu_pct",
                     "decode_attn_roofline", "prefill_attn_roofline",
                     "decode_batch_mean"], r) == {
        "device_idle_pct": 4.592761057575379,
        "step_mfu_pct": 0.35927427507967385,
        "decode_attn_roofline": 9.807114675123612,
        "prefill_attn_roofline": None, "decode_batch_mean": 8.0}


class _PerStep(list):
    """A step log that also keeps each step's entries apart."""

    def __init__(self):
        super().__init__()
        self.steps = []

    def extend(self, items):
        items = list(items)
        self.steps.append(items)
        super().extend(items)


def test_launch_spans_and_counters_equal_the_step_log(checkout, tmp_path):
    """The engine's ``engine.prefill``/``engine.decode`` args, step for
    step, and its launch counters over the window, equal what the
    benchmark's step log (``run.step_work``) rebuilds from the client
    stream and the engine's request state."""
    import time

    from jax.profiler import ProfileData

    import run
    from manifest import Manifest
    man = Manifest(checkout)
    cell = man.cell(CHAT)
    cfg, mix = man.config(cell), man.traffic(cell)
    mix["engine"]["num_pages"] = 200       # no preemption: a recomputed
    core, schedule = run.prepare(          # prefill is not in the step log
        cfg, mix, 5, man.generator(mix["kind"]), [])
    log = _PerStep()
    trace_dir = str(tmp_path / "trace")
    out = run.drive(core, schedule, 2.0, trace_dir, time.perf_counter,
                    launches=log)
    assert out["steps"] == len(log.steps) > 0
    pd = ProfileData.from_file(tr.find_xplane(trace_dir))
    evs = sorted((e.start_ns, e.name, dict(e.stats))
                 for p in pd.planes if p.name.startswith("/host:")
                 for line in p.lines for e in line.events
                 if e.name in ("engine.step", "engine.prefill",
                               "engine.decode"))
    steps = []
    for _, name, args in evs:
        if name == "engine.step":
            steps.append({"tokens": 0, "attended": 0, "prefill": 0,
                          "decode": None})
        elif name == "engine.prefill":
            steps[-1]["tokens"] += args["tokens"]
            steps[-1]["attended"] += args["attended"]
            steps[-1]["prefill"] += 1
        else:
            steps[-1]["decode"] = (args["rows"], args["ctx_tokens"])
    assert len(steps) == len(log.steps)
    for got, entries in zip(steps, log.steps):
        want = {"tokens": 0, "attended": 0, "decode": None}
        for e in entries:
            if e["kind"] == "prefill":
                for start, n in e["rows"]:
                    want["tokens"] += n
                    want["attended"] += n * start + n * (n + 1) // 2
            else:
                want["decode"] = (len(e["ctx"]), sum(e["ctx"]))
        got = dict(got)
        del got["prefill"]
        assert got == want
    m = core.metrics
    dec = [s["decode"] for s in steps if s["decode"]]
    assert m["engine_decode_launches_total"].window == len(dec) > 0
    assert m["engine_decode_rows_total"].window == sum(r for r, _ in dec)
    assert m["engine_prefill_tokens_total"].window \
        == sum(s["tokens"] for s in steps) > 0
    assert m["engine_prefill_launches_total"].window \
        == sum(s["prefill"] for s in steps)
    assert m["pressure_preemptions_total"].window == 0


def test_recorded_chat_trace_split_sums_to_device_idle():
    """Four steps of qwen2.5-32b-l8.chat-sessions on one TPU v5 lite
    (seed 1301, traced mid-window), recorded with the engine's spans on
    the host: the device's modules, its kernel calls, loops and
    instructions of 20 us or more, and the host's step and engine spans
    (``engine_spans.read_xplane``)."""
    with open(os.path.join(DATA, "trace_v5e_qwen_chat_4steps.json")) as f:
        t = tr.Trace.from_dict(json.load(f))
    r = tr.reduce_trace(t, ["paged_flash_decode_fwd", "paged_prefill_fwd"])
    assert r.steps == 4
    assert r.kernel_calls == {"paged_flash_decode_fwd": 32,
                              "paged_prefill_fwd": 16}
    got = es.split(t)
    idle_pct = 100.0 * r.idle_share          # device_idle_pct.chat
    assert 100.0 * sum(got.values()) / r.window_s == pytest.approx(
        idle_pct, abs=1e-9)
    sample = es.idle_pct(got, r.window_s, es.SAMPLE)
    launch = es.idle_pct(got, r.window_s, es.LAUNCH)
    # the mixed-sampling steps wait in the logits pull and row sampling
    assert max(got, key=got.get) == "engine.sample"
    assert sample > 0.9 * idle_pct and 0.0 < launch < 0.05 * idle_pct
    # labelled by their midpoints, the longest gaps name engine spans
    assert {lab for lab, _ in r.idle_gaps[:4]} <= set(es.SAMPLE)
