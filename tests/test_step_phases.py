"""Engine step phases on the profiler clock (``serving/metrics.py:
StepPhases``).

Under ``jax.profiler`` every phase of ``EngineCore.step()`` is a host
span named ``engine.<phase>``, nested in ``engine.step``, on the clock
of the device's events; the launch spans carry each launch's work as
integer args, which the registry also counts.  ``launch/serve.py
--metrics`` still prints the Prometheus exposition and writes the flight
recorder's Chrome trace.
"""
import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.config import ParallelConfig, ServeConfig, get_model_config, \
    reduce_for_smoke
from repro.serving.core import EngineCore
from repro.serving.metrics import StepPhases
from repro.serving.scheduler import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REQUIRED = {"engine.schedule", "engine.swap", "engine.grow",
            "engine.cow_replay", "engine.prefill", "engine.decode",
            "engine.guard", "engine.sample", "engine.stream",
            "engine.verify"}


@pytest.fixture(scope="module")
def built():
    from repro.models import build_model
    cfg = reduce_for_smoke(get_model_config("gemma2-2b"))
    model = build_model(cfg, ParallelConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


def _core(built, **serve_kw):
    model, params, cfg = built
    kw = dict(max_batch=3, max_seq_len=96, page_size=16, prefill_chunk=16,
              prefix_cache=True)
    kw.update(serve_kw)
    return EngineCore(model, params, cfg, ServeConfig(**kw))


def _drain(core):
    while core.has_work:
        core.step()


def _serve(core, cfg, rng):
    """Mixed greedy and sampled requests, then a page-aligned prompt
    served twice: the second hits the prefix cache in full and copies
    its shared tail page before writing (copy-on-write)."""
    for i in range(3):
        sp = (SamplingParams(max_new_tokens=4) if i != 1 else
              SamplingParams(temperature=0.8, top_k=8, seed=5,
                             max_new_tokens=4))
        core.add_request(rng.integers(0, cfg.vocab_size, size=5 + 9 * i),
                         sp)
    _drain(core)
    prompt = rng.integers(0, cfg.vocab_size, size=32)
    for _ in range(2):
        core.add_request(prompt, SamplingParams(max_new_tokens=3))
        _drain(core)


def _host_events(log_dir):
    """``(line, start_ns, end_ns, name, args)`` of every ``engine.*``
    event on the host planes of the profile in ``log_dir``."""
    from jax.profiler import ProfileData
    [path] = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((li, e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return out


def test_every_phase_is_a_profiler_span_inside_engine_step(built, tmp_path):
    _, _, cfg = built
    rng = np.random.default_rng(0)
    plain = _core(built)
    spec = _core(built, spec_mode="lookup", spec_tokens=2)
    with jax.profiler.trace(str(tmp_path)):
        _serve(plain, cfg, rng)
        _serve(spec, cfg, rng)
    evs = _host_events(str(tmp_path))
    steps = [e for e in evs if e[3] == "engine.step"]
    assert len(steps) == plain.steps + spec.steps
    phases = [e for e in evs if e[3] != "engine.step"]
    assert {e[3] for e in phases} == REQUIRED
    for line, s, t, name, _ in phases:
        assert any(sl == line and ss <= s and t <= st
                   for sl, ss, st, _, _ in steps), f"{name} outside a step"
    # the launch spans carry their work as integer args
    assert all(set(e[4]) == {"rows", "ctx_tokens"}
               for e in phases if e[3] == "engine.decode")
    assert all(set(e[4]) == {"rows", "tokens", "attended"}
               for e in phases if e[3] == "engine.prefill")


def test_launch_counters_match_the_span_args(built, tmp_path):
    _, _, cfg = built
    core = _core(built)
    with jax.profiler.trace(str(tmp_path)):
        _serve(core, cfg, np.random.default_rng(1))
    evs = _host_events(str(tmp_path))
    dec = [e[4] for e in evs if e[3] == "engine.decode"]
    pre = [e[4] for e in evs if e[3] == "engine.prefill"]
    m = core.metrics
    assert m["engine_decode_launches_total"].window == len(dec) > 0
    assert m["engine_decode_rows_total"].window == sum(a["rows"] for a in dec)
    assert m["engine_prefill_launches_total"].window == len(pre) \
        == core.prefill_launches > 0
    assert m["engine_prefill_tokens_total"].window \
        == sum(a["tokens"] for a in pre)
    # the repeated prompt's second prefill is one token past a 31-token
    # hit: attended = n * start + n (n + 1) / 2
    assert {"rows": 1, "tokens": 1, "attended": 32} in pre
    # each row's tokens attend to themselves at least
    assert all(a["attended"] >= a["tokens"] >= a["rows"] for a in pre)


def test_phase_durations_are_bracketed_and_clock_free_when_off():
    """A phase's duration is the engine clock over its region, summed
    per phase; nested phases count in their parent too.  Without a
    phase dict no clock is read."""
    reads = []

    def clock():
        reads.append(None)
        return float(len(reads))

    ph = StepPhases(clock)
    times = {}
    with ph.step(times):
        with ph("grow"):             # reads 1 .. 4
            with ph("cow_replay"):   # reads 2, 3
                pass
        with ph("decode") as span:   # reads 5, 6
            span.args(rows=2, ctx_tokens=9)
        with ph("grow"):             # reads 7, 8
            pass
    assert times == {"cow_replay": 1.0, "grow": 4.0, "decode": 1.0}
    assert ph.times is None
    n = len(reads)
    with ph.step(None):
        with ph("decode"):
            pass
    assert len(reads) == n


def test_serve_metrics_prints_prometheus_and_writes_chrome_trace(tmp_path):
    out = tmp_path / "trace.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "gemma2-2b",
         "--smoke", "--stream", "--metrics", str(out)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "# TYPE engine_phase_decode_seconds histogram" in proc.stdout
    assert "engine_decode_rows_total " in proc.stdout
    with open(out) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]
             if e.get("cat") == "phase"}
    assert {"schedule", "prefill", "decode", "sample", "stream"} <= names
