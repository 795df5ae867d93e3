"""Serving benchmark: paged KV + continuous batching + chunked prefill.

Three sections, emitted together as machine-readable
``BENCH_serving.json`` at the repo root (the perf baseline future PRs
regress against):

* **mixed traffic** -- streams a queue of requests with randomised
  prompt/generation lengths through ``ServeEngine.generate_stream`` and
  reports decode throughput, per-request time-to-first-token (TTFT)
  and page-pool pressure.  The pool is deliberately sized
  *below* ``max_batch * max_seq_len``: the scheduler trades a longer
  queue for a hard memory ceiling a dense static-batch engine cannot
  offer at all.
* **prefill** -- one long prompt through the legacy scan prefill (one
  decode step per token, PR 1) vs chunked paged prefill (fixed-size
  chunks through the full tiled forward), reporting prefill tokens/s and
  the chunked/scan speedup.
* **oversubscription** -- offered load deliberately exceeds the pool
  (every request realises its worst case; the pool holds ``pool_frac``
  of the total demand).  Runs the same workload under the PR 1
  worst-case-reservation admission and under optimistic admission with
  preemption (swap-to-host / recompute), reporting preemption counts,
  swap bytes and the pool high-water-mark: reservation leaves the pool
  under-subscribed, pressure-managed admission drives it to ~100% with
  zero caller-visible failures.
* **prefix_sharing** -- N requests share a long system prompt.  A cold
  engine (no prefix cache) prefills every prompt from token 0; a warm
  engine (``prefix_cache=True``, radix index seeded by a first run)
  shares the cached system-prompt pages copy-on-write and computes only
  each request's unique tail.  Reports prefill tokens computed, TTFT
  and pages resident both ways; greedy tokens must be bit-identical.
* **open_loop** -- drives ``EngineCore.step()`` directly under a
  deterministic (seeded) Poisson arrival schedule with mixed per-request
  ``SamplingParams`` (greedy and seeded temperature sampling): requests
  arrive *while* the engine runs, instead of all up front.  Reports
  TTFT and TPOT (time per output token) p50/p99 -- the latency numbers
  an iteration-level engine exists for.
* **degradation** -- over-offered Poisson load (arrivals faster than
  the engine drains) through ``EngineCore.step()``, unbounded vs
  bounded (``max_waiting`` + ``queue_policy="shed_oldest"`` +
  per-request ``deadline_ms``).  Reports shed rate, timed-out count and
  the *survivors'* TTFT/TPOT p99 both ways: load shedding must keep the
  survivor tail flat while the unbounded engine's queueing latency
  grows without bound.
* **observability** -- telemetry overhead: the open-loop workload driven
  twice through ``EngineCore.step()``, once with the metrics registry /
  lifecycle tracer / flight recorder enabled (``metrics=True``, the
  default) and once fully disabled, reporting best-of-N ms/step both
  ways and the on/off overhead ratio (CI gates it at <= 1.05).  The
  metrics-on run also exports the flight recorder's Chrome
  ``trace_event`` JSON as ``BENCH_serving_trace.json``.
* **distributed** -- tensor-parallel serving on a forced multi-device
  CPU mesh (a child process under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``): the paged
  engine sharded 2- and 4-way (kv-head groups x page-row sub-shards,
  partial attention merged via the LSE combination) must emit greedy
  tokens bit-identical to the single-device engine, and the section
  times the tp=4 engine under the paper's tiling-AllReduce (§4.2 T3)
  against the monolithic single-AllReduce baseline.

    PYTHONPATH=src python -m benchmarks.serving_bench \
        [--arch gemma2-2b] [--requests 12] [--prefill-len 512]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from repro.config import ParallelConfig, ServeConfig, get_model_config, \
    reduce_for_smoke
from repro.models import build_model
from repro.serving.core import EngineCore
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import Request, SamplingParams

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_config(cfg):
    """A 'small' (not unit-test-tiny) CPU config for the prefill timing:
    reduce_for_smoke is sized for test latency, and at that width the
    per-step overhead of the scan baseline masks the batching win the
    chunked path exists for.  Keeps GQA ratio / window / softcap."""
    cfg = reduce_for_smoke(cfg)
    kv = cfg.num_kv_heads
    heads = cfg.num_heads
    head_dim = 32
    return dataclasses.replace(
        cfg, num_layers=4, d_model=heads * head_dim, head_dim=head_dim,
        d_ff=4 * heads * head_dim if cfg.d_ff else 0, vocab_size=1024,
        window_size=128 if cfg.window_size else None)


def _warm(engine, cfg, serve, rng):
    """Compile everything the timed region will hit: the fused decode
    step, a multi-chunk prompt, and every power-of-two batched-prefill
    launch width up to max_batch (w concurrent short prompts prefill in
    one step -> one width-w launch).  The engine core is persistent, so
    the serving state (peak pages, pressure stats, any prefix blocks
    the warmup published) is reset afterwards -- the reported metrics
    must cover only the timed workload; jit caches survive the reset."""
    widths, w = [], 1
    while w < serve.max_batch:
        widths.append(w)
        w *= 2
    widths.append(serve.max_batch)
    wid = -1
    for w in widths:
        warms = []
        for i in range(w):
            wid -= 1
            n = min(serve.prefill_chunk_tokens + 1,
                    serve.max_seq_len - 2) if (w == 1 and i == 0) else 3 + i
            warms.append(Request(id=wid, prompt=rng.integers(
                0, cfg.vocab_size, size=n), max_new_tokens=2))
        list(engine.generate_stream(warms))
    engine.core.reset()
    # open a fresh metrics window too: registry counters, the step-time
    # high water and the flight recorder survive reset() (they are
    # engine-lifetime, like the jit caches) and would otherwise report
    # warmup compile steps as part of the timed workload
    engine.core.reset_metrics_window()


def _build(arch: str, smoke: bool, small: bool = False):
    cfg = get_model_config(arch)
    if small and smoke:
        cfg = _small_config(cfg)
    elif smoke:
        cfg = reduce_for_smoke(cfg)
    model = build_model(cfg, ParallelConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def run(arch: str = "gemma2-2b", n_requests: int = 12, max_batch: int = 4,
        page_size: int = 0, max_seq_len: int = 128, pool_frac: float = 0.6,
        seed: int = 0, smoke: bool = True, built=None) -> dict:
    """Mixed-length-traffic section."""
    # 0 = auto: the TPU kernel needs lane-width (128) pages; CPU smoke
    # runs use small pages so slot/page churn actually happens
    page_size = page_size or (
        128 if jax.default_backend() == "tpu" else 16)
    max_seq_len = max(max_seq_len, 2 * page_size)
    cfg, model, params = built or _build(arch, smoke)

    dense_pages = max_batch * (-(-max_seq_len // page_size))
    num_pages = max(4, int(dense_pages * pool_frac)) + 1
    serve = ServeConfig(max_batch=max_batch, max_seq_len=max_seq_len,
                        top_k=1, page_size=page_size, num_pages=num_pages)
    engine = ServeEngine(model=model, params=params, cfg=cfg, serve=serve)

    rng = np.random.default_rng(seed)
    # mixed traffic: short chats + a few long-prompt / long-generation jobs
    reqs = []
    for i in range(n_requests):
        if i % 4 == 3:
            s = int(rng.integers(max_seq_len // 4, max_seq_len // 2))
            n = int(rng.integers(8, max(9, max_seq_len // 4)))
        else:
            s = int(rng.integers(2, max(3, max_seq_len // 8)))
            n = int(rng.integers(2, 16))
        n = max(1, min(n, max_seq_len - s))
        reqs.append(Request(id=i, prompt=rng.integers(
            0, cfg.vocab_size, size=s), max_new_tokens=n))

    # warmup: every batched-prefill width + multi-chunk prefill + fused
    # decode, so the timed region is not compile-dominated
    _warm(engine, cfg, serve, rng)

    t0 = time.perf_counter()
    ttft = {}
    events = []
    for ev in engine.generate_stream(reqs):
        if ev.index == 0:
            ttft[ev.request_id] = time.perf_counter() - t0
        events.append(ev)
    dt = time.perf_counter() - t0

    mgr = engine.last_cache
    total_new = sum(r.max_new_tokens for r in reqs)
    assert len(events) == total_new
    assert all(r.state == "FINISHED" for r in reqs)
    assert mgr.used_pages == 0, "pages leaked after drain"
    assert mgr.peak_used_pages <= num_pages - 1, "pool ceiling violated"

    tt = np.asarray(sorted(ttft.values()))
    stats = {
        "requests": n_requests,
        "generated_tokens": total_new,
        "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
        "wall_s": round(dt, 3),
        "tokens_per_s": round(total_new / dt, 1),
        # TTFT includes queueing: requests that wait for a slot pay it
        "ttft_mean_s": round(float(tt.mean()), 4),
        "ttft_p50_s": round(float(np.median(tt)), 4),
        "ttft_max_s": round(float(tt.max()), 4),
        "pool_pages": num_pages - 1,
        "dense_equiv_pages": dense_pages,
        "peak_pages": mgr.peak_used_pages,
        "peak_kv_frac_of_dense": round(
            mgr.peak_used_pages / dense_pages, 3),
        # the persistent core also counts warmup requests in
        # sched.finished; report this call's completions
        "finished": sum(1 for r in reqs if r.state == "FINISHED"),
    }
    return stats


def prefill_bench(arch: str = "gemma2-2b", prompt_len: int = 512,
                  page_size: int = 0, prefill_chunk: int = 0,
                  seed: int = 0, smoke: bool = True, built=None) -> dict:
    """Chunked vs scan prefill throughput (and TTFT) on one long prompt."""
    page_size = page_size or (
        128 if jax.default_backend() == "tpu" else 16)
    cfg, model, params = built or _build(arch, smoke, small=True)
    max_seq_len = prompt_len + 2 * page_size
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, size=prompt_len)

    out = {"prompt_tokens": prompt_len, "d_model": cfg.d_model,
           "num_layers": cfg.num_layers}
    for mode in ("scan", "chunked"):
        serve = ServeConfig(max_batch=1, max_seq_len=max_seq_len, top_k=1,
                            page_size=page_size, prefill_mode=mode,
                            prefill_chunk=prefill_chunk)
        engine = ServeEngine(model=model, params=params, cfg=cfg,
                             serve=serve)
        times = []
        for rep in range(2):       # rep 0 is the compile warmup
            req = Request(id=rep, prompt=prompt, max_new_tokens=1)
            t0 = time.perf_counter()
            list(engine.generate_stream([req]))
            times.append(time.perf_counter() - t0)
        best = min(times[1:])
        # one request, one new token: the whole wall time is TTFT
        out[mode] = {
            "ttft_s": round(best, 4),
            "tokens_per_s": round(prompt_len / best, 1),
        }
        if mode == "chunked":
            out["prefill_chunk"] = serve.prefill_chunk_tokens
            out["kernel_launches"] = -(-prompt_len
                                       // serve.prefill_chunk_tokens)
    out["chunked_speedup_vs_scan"] = round(
        out["scan"]["ttft_s"] / out["chunked"]["ttft_s"], 2)
    return out


def oversubscribe(arch: str = "gemma2-2b", n_requests: int = 8,
                  max_batch: int = 6, page_size: int = 0,
                  max_seq_len: int = 64, pool_frac: float = 0.6,
                  preempt_policy: str = "swap", seed: int = 0,
                  smoke: bool = True, built=None) -> dict:
    """Offered load > pool capacity: reservation baseline vs optimistic
    admission + preemption on the identical workload and pool.  Needs
    enough decode slots that the *concurrent* demand of the slots can
    exceed the pool -- otherwise nothing ever pressures it."""
    page_size = page_size or (
        128 if jax.default_backend() == "tpu" else 16)
    max_seq_len = max(max_seq_len, 4 * page_size)
    cfg, model, params = built or _build(arch, smoke)

    def make_requests():
        # fresh rng per run: both admission policies must see the
        # identical workload.  Every request runs to max_new_tokens (no
        # eos), so the offered worst-case demand is fully realised.
        rng = np.random.default_rng(seed)
        reqs = []
        for i in range(n_requests):
            if i % 3 == 0:
                s = int(rng.integers(max_seq_len // 4, max_seq_len // 2))
            else:
                s = int(rng.integers(4, max(5, max_seq_len // 8)))
            reqs.append(Request(id=i, prompt=rng.integers(
                0, cfg.vocab_size, size=s),
                max_new_tokens=max_seq_len - s))
        return reqs

    worst_pages = sum(-(-r.target_len // page_size)
                      for r in make_requests())
    num_pages = int(worst_pages * pool_frac) + 1

    out = {
        "requests": n_requests,
        "worst_case_pages": worst_pages,
        "pool_pages": num_pages - 1,
        "pool_frac_of_worst": round((num_pages - 1) / worst_pages, 3),
        "preempt_policy": preempt_policy,
    }
    for admission in ("reserved", "optimistic"):
        serve = ServeConfig(max_batch=max_batch, max_seq_len=max_seq_len,
                            top_k=1, page_size=page_size,
                            num_pages=num_pages, admission=admission,
                            preempt_policy=preempt_policy)
        engine = ServeEngine(model=model, params=params, cfg=cfg,
                             serve=serve)
        _warm(engine, cfg, serve, np.random.default_rng(seed + 1))
        reqs = make_requests()
        failures, error = 0, None
        t0 = time.perf_counter()
        try:
            events = list(engine.generate_stream(reqs))
        except Exception as e:         # count AND surface caller failures
            failures, events, error = 1, [], repr(e)
        dt = time.perf_counter() - t0
        mgr, pressure = engine.last_cache, engine.last_pressure
        total_new = sum(r.max_new_tokens for r in reqs)
        out[admission] = {
            "completed": sum(1 for r in reqs if r.state == "FINISHED"),
            "caller_failures": failures,
            "error": error,
            "generated_tokens": len(events),
            "wall_s": round(dt, 3),
            "tokens_per_s": round(total_new / dt, 1),
            "preemptions": pressure.stats["preemptions"],
            "swaps": pressure.stats["swaps"],
            "recomputes": pressure.stats["recomputes"],
            "swap_bytes_out": pressure.stats["swap_bytes_out"],
            "swap_bytes_in": pressure.stats["swap_bytes_in"],
            "host_pool_peak_pages": pressure.host_pool.peak_pages,
            "peak_pages": mgr.peak_used_pages,
            "peak_utilization": round(mgr.peak_utilization, 3),
            "pages_leaked": mgr.used_pages,
        }
    return out


def prefix_sharing(arch: str = "gemma2-2b", n_requests: int = 6,
                   system_len: int = 96, unique_len: int = 12,
                   max_batch: int = 3, page_size: int = 0,
                   max_new: int = 4, seed: int = 0, smoke: bool = True,
                   built=None) -> dict:
    """Shared-system-prompt workload, cold (no prefix cache) vs warm
    (radix index seeded by a prior run on the same engine)."""
    page_size = page_size or (
        128 if jax.default_backend() == "tpu" else 16)
    system_len = max(system_len, 2 * page_size)
    cfg, model, params = built or _build(arch, smoke)
    max_seq_len = system_len + unique_len + max_new + page_size
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size, size=system_len)

    def make_requests(run_seed):
        r = np.random.default_rng(run_seed)
        return [Request(id=i, prompt=np.concatenate(
            [sys_prompt, r.integers(0, cfg.vocab_size, size=unique_len)]),
            max_new_tokens=max_new) for i in range(n_requests)]

    def serve_cfg(prefix):
        return ServeConfig(max_batch=max_batch, max_seq_len=max_seq_len,
                           top_k=1, page_size=page_size,
                           prefix_cache=prefix)

    def timed_run(engine, reqs):
        failures, error, events, ttft = 0, None, [], {}
        t0 = time.perf_counter()
        try:
            for ev in engine.generate_stream(reqs):
                if ev.index == 0:
                    ttft[ev.request_id] = time.perf_counter() - t0
                events.append(ev)
        except Exception as e:
            failures, error = 1, repr(e)
        dt = time.perf_counter() - t0
        computed = sum(len(r.prompt) - r.matched_len for r in reqs)
        mgr = engine.last_cache
        return {
            "completed": sum(1 for r in reqs if r.state == "FINISHED"),
            "caller_failures": failures,
            "error": error,
            "wall_s": round(dt, 3),
            "ttft_mean_s": round(float(np.mean(list(ttft.values()))), 4)
            if ttft else None,
            "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
            "prefill_tokens_computed": int(computed),
            "matched_tokens": int(sum(r.matched_len for r in reqs)),
            "pages_resident": mgr.used_pages,
        }, [r.generated for r in reqs]

    shared_aligned = (system_len // page_size) * page_size
    out = {
        "requests": n_requests,
        "system_prompt_tokens": system_len,
        "unique_tokens": unique_len,
        "shared_aligned_tokens": shared_aligned,
        # the fraction of prefill work the cache should at least save
        "shared_prefix_fraction": round(
            shared_aligned / (system_len + unique_len), 3),
    }

    # cold: no prefix cache, every prompt prefills from token 0
    cold = ServeEngine(model=model, params=params, cfg=cfg,
                       serve=serve_cfg(False))
    _warm(cold, cfg, cold.serve, np.random.default_rng(seed + 1))
    out["cold"], cold_tokens = timed_run(cold, make_requests(seed + 2))

    # warm: same engine config with the radix index, seeded by one run
    eng = ServeEngine(model=model, params=params, cfg=cfg,
                      serve=serve_cfg(True))
    _warm(eng, cfg, eng.serve, np.random.default_rng(seed + 1))
    out["seed_run"], _ = timed_run(eng, make_requests(seed + 3))
    out["warm"], warm_tokens = timed_run(eng, make_requests(seed + 2))
    out["cached_pages"] = eng.last_prefix.cached_pages
    out["tokens_bit_identical"] = bool(warm_tokens == cold_tokens)
    return out


def open_loop(arch: str = "gemma2-2b", n_requests: int = 10,
              max_batch: int = 3, page_size: int = 0,
              max_seq_len: int = 96, mean_gap_steps: float = 2.0,
              seed: int = 0, smoke: bool = True, built=None) -> dict:
    """Open-loop serving through ``EngineCore.step()``: a deterministic
    seeded Poisson process schedules arrivals *by engine step* (each
    inter-arrival gap ~ Exp(mean_gap_steps)), requests carry mixed
    SamplingParams (greedy chats and seeded sampling jobs), and the
    driver measures what a frontend would: TTFT (arrival -> first
    token) and TPOT (mean gap between a request's tokens)."""
    page_size = page_size or (
        128 if jax.default_backend() == "tpu" else 16)
    max_seq_len = max(max_seq_len, 4 * page_size)
    cfg, model, params = built or _build(arch, smoke)

    serve = ServeConfig(max_batch=max_batch, max_seq_len=max_seq_len,
                        page_size=page_size,
                        num_pages=max_batch * 3 + 1)   # undersized: churn
    core = EngineCore(model, params, cfg, serve)

    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(
        rng.exponential(scale=mean_gap_steps, size=n_requests))).astype(int)
    specs = []
    for i in range(n_requests):
        s = int(rng.integers(3, max_seq_len // 3))
        n = int(rng.integers(4, max(5, (max_seq_len - s) // 2)))
        if i % 3 == 2:                 # every 3rd request samples
            sp = SamplingParams(temperature=0.8, top_k=8, seed=seed + i,
                                max_new_tokens=n)
        else:
            sp = SamplingParams(max_new_tokens=n)
        specs.append((rng.integers(0, cfg.vocab_size, size=s), sp))

    # warmup: compile the decode step and every chunk-launch width the
    # schedule may hit, then reset the serving state (jit caches stay)
    widths, w = [], 1
    while w < max_batch:
        widths.append(w)
        w *= 2
    widths.append(max_batch)
    wid = 0
    for w in widths:
        for i in range(w):
            wid -= 1
            core.add_request(rng.integers(0, cfg.vocab_size, size=3 + i),
                             SamplingParams(max_new_tokens=2),
                             request_id=wid)
        while core.has_work:
            core.step()
    core.reset()
    core.reset_metrics_window()   # drop warmup from the metrics window

    t_arrive, t_first, t_last, n_toks = {}, {}, {}, {}
    next_req = 0
    step_idx = 0
    t0 = time.perf_counter()
    while next_req < n_requests or core.has_work:
        while next_req < n_requests and arrivals[next_req] <= step_idx:
            prompt, sp = specs[next_req]
            rid = core.add_request(prompt, sp, request_id=next_req)
            t_arrive[rid] = time.perf_counter()
            next_req += 1
        for ev in core.step():
            now = time.perf_counter()
            t_first.setdefault(ev.request_id, now)
            t_last[ev.request_id] = now
            n_toks[ev.request_id] = n_toks.get(ev.request_id, 0) + 1
        step_idx += 1
    wall = time.perf_counter() - t0

    assert len(t_first) == n_requests, "some request never produced"
    assert core.mgr.used_pages == 0, "pages leaked after drain"
    stats = core.stats()
    assert stats["finished"] == n_requests

    ttft = np.asarray([t_first[i] - t_arrive[i] for i in range(n_requests)])
    tpot = np.asarray([(t_last[i] - t_first[i]) / (n_toks[i] - 1)
                       for i in range(n_requests) if n_toks[i] > 1])
    total_toks = sum(n_toks.values())
    # engine-native latencies from the lifecycle tracer, stamped on the
    # engine's own clock at submit/first-token/last-token (warmup
    # requests were cleared by reset_metrics_window).  Stamps are taken
    # *inside* the step, so work that lands later in the same step (a
    # cold sampling compile, another request's prefill) never inflates
    # them -- the step-granular driver above can only observe tokens
    # after step() returns and lumps that in.  tests/test_metrics.py
    # proves exact engine-vs-bench agreement under a manual clock.
    done = [r for r in core.tracer.completed if r["reason"] == "finished"]
    nat_ttft = np.asarray([r["first_token_t"] - r["submit_t"]
                           for r in done])
    nat_tpot = np.asarray([
        (r["last_token_t"] - r["first_token_t"]) / (r["n_tokens"] - 1)
        for r in done if r["n_tokens"] > 1])
    return {
        "requests": n_requests,
        "mean_gap_steps": mean_gap_steps,
        "engine_steps": stats["steps"],
        "generated_tokens": total_toks,
        "sampled_requests": sum(1 for _, sp in specs if not sp.greedy),
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_toks / wall, 1),
        "ttft_p50_s": round(float(np.percentile(ttft, 50)), 4),
        "ttft_p99_s": round(float(np.percentile(ttft, 99)), 4),
        "tpot_p50_s": round(float(np.percentile(tpot, 50)), 4),
        "tpot_p99_s": round(float(np.percentile(tpot, 99)), 4),
        "engine_ttft_p50_s": round(float(np.percentile(nat_ttft, 50)), 4),
        "engine_ttft_p99_s": round(float(np.percentile(nat_ttft, 99)), 4),
        "engine_tpot_p50_s": round(float(np.percentile(nat_tpot, 50)), 4),
        "engine_open_spans_after_drain": core.tracer.open_span_count(),
        "preemptions": stats["pressure"]["preemptions"],
        "peak_utilization": round(stats["peak_utilization"], 3),
    }


def degradation(arch: str = "gemma2-2b", n_requests: int = 14,
                max_batch: int = 3, page_size: int = 0,
                max_seq_len: int = 96, mean_gap_steps: float = 0.5,
                deadline_ms: float = 1000.0, max_waiting: int = 2,
                seed: int = 0, smoke: bool = True, built=None) -> dict:
    """Graceful degradation under over-offered load: the same seeded
    Poisson arrival schedule (arrivals ~2x faster than the engine
    drains) driven through ``EngineCore.step()`` twice -- once
    *unbounded* (every request queues forever, no deadline) and once
    *bounded* (``max_waiting`` + ``queue_policy="shed_oldest"`` +
    per-request ``deadline_ms``).  The unbounded engine completes
    everything at the cost of unbounded queueing latency; the bounded
    engine sheds excess load with structured errors and keeps the
    survivors' TTFT/TPOT tail flat.  The CI artifact check gates on the
    survivors' p99 not regressing past the unbounded baseline."""
    page_size = page_size or (
        128 if jax.default_backend() == "tpu" else 16)
    max_seq_len = max(max_seq_len, 4 * page_size)
    cfg, model, params = built or _build(arch, smoke)

    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(
        rng.exponential(scale=mean_gap_steps, size=n_requests))).astype(int)
    specs = [(rng.integers(0, cfg.vocab_size,
                           size=int(rng.integers(4, max_seq_len // 3))),
              int(rng.integers(6, 14))) for _ in range(n_requests)]

    def drive(bounded: bool) -> dict:
        serve = ServeConfig(
            max_batch=max_batch, max_seq_len=max_seq_len,
            page_size=page_size, num_pages=max_batch * 3 + 1,
            max_waiting=max_waiting if bounded else 0,
            queue_policy="shed_oldest")
        core = EngineCore(model, params, cfg, serve)
        # warmup: decode + every chunk-launch width, then reset state
        wid = 0
        for w in (1, 2, max_batch):
            for i in range(w):
                wid -= 1
                core.add_request(
                    rng.integers(0, cfg.vocab_size, size=3 + i),
                    SamplingParams(max_new_tokens=2), request_id=wid)
            while core.has_work:
                core.step()
        core.reset()
        # fresh metrics window: ``step_s_high_water`` below must be the
        # timed workload's slowest step, not the warmup's compile steps
        # (which dwarf every steady-state step and used to mask it)
        core.reset_metrics_window()

        t_arrive, t_first, t_last, n_toks = {}, {}, {}, {}
        next_req, step_idx, waiting_hw = 0, 0, 0
        t0 = time.perf_counter()
        while next_req < n_requests or core.has_work:
            while next_req < n_requests and arrivals[next_req] <= step_idx:
                prompt, n = specs[next_req]
                sp = SamplingParams(
                    max_new_tokens=n,
                    deadline_ms=deadline_ms if bounded else None)
                core.add_request(prompt, sp, request_id=next_req)
                t_arrive[next_req] = time.perf_counter()
                next_req += 1
            for ev in core.step():
                if ev.kind != "token":
                    continue
                now = time.perf_counter()
                t_first.setdefault(ev.request_id, now)
                t_last[ev.request_id] = now
                n_toks[ev.request_id] = n_toks.get(ev.request_id, 0) + 1
            waiting_hw = max(waiting_hw, len(core.sched.waiting))
            step_idx += 1
        wall = time.perf_counter() - t0
        assert core.mgr.used_pages == 0, "pages leaked after drain"

        stats = core.stats()
        health = stats["health"]
        done = sorted(r.id for r in core.sched.finished if r.id >= 0)
        ttft = np.asarray([t_first[i] - t_arrive[i] for i in done])
        tpot = np.asarray([(t_last[i] - t_first[i]) / (n_toks[i] - 1)
                           for i in done if n_toks.get(i, 0) > 1])
        total = sum(n_toks.values())
        out = {
            "completed": len(done),
            "shed": health["shed"],
            "timed_out": health["timed_out"],
            "failed": health["failed"],
            "waiting_high_water": waiting_hw,
            "engine_steps": stats["steps"],
            "generated_tokens": total,
            "wall_s": round(wall, 3),
            "survivor_ttft_p50_s": round(
                float(np.percentile(ttft, 50)), 4),
            "survivor_ttft_p99_s": round(
                float(np.percentile(ttft, 99)), 4),
            "survivor_tpot_p50_s": round(
                float(np.percentile(tpot, 50)), 4),
            "survivor_tpot_p99_s": round(
                float(np.percentile(tpot, 99)), 4),
            "step_s_high_water": round(health["step_s_high_water"], 4),
        }
        if bounded:
            out["shed_rate"] = round(
                (health["shed"] + health["timed_out"]) / n_requests, 3)
        return out

    report = {
        "requests": n_requests,
        "mean_gap_steps": mean_gap_steps,
        "deadline_ms": deadline_ms,
        "max_waiting": max_waiting,
        "queue_policy": "shed_oldest",
        "unbounded": drive(False),
        "bounded": drive(True),
    }
    b, u = report["bounded"], report["unbounded"]
    assert u["completed"] == n_requests, "unbounded baseline lost requests"
    assert b["completed"] + b["shed"] + b["timed_out"] == n_requests
    report["survivor_ttft_p99_ratio"] = round(
        b["survivor_ttft_p99_s"] / u["survivor_ttft_p99_s"], 3)
    report["survivor_tpot_p99_ratio"] = round(
        b["survivor_tpot_p99_s"] / u["survivor_tpot_p99_s"], 3)
    return report


def observability(arch: str = "gemma2-2b", n_requests: int = 10,
                  max_batch: int = 3, page_size: int = 0,
                  max_seq_len: int = 96, mean_gap_steps: float = 2.0,
                  repeats: int = 2, seed: int = 0, smoke: bool = True,
                  built=None, trace_out: str = "") -> dict:
    """Telemetry overhead: the open-loop workload through
    ``EngineCore.step()`` with the full telemetry stack on
    (``metrics=True``: registry, lifecycle tracer, flight recorder,
    per-step phase histograms) vs completely off, best-of-``repeats``
    ms/step each way.  The instrumentation is all host-side Python
    between launches, so the ratio must stay ~1.0; CI gates it at 1.05.
    The metrics-on run also dumps the flight recorder's Chrome trace."""
    page_size = page_size or (
        128 if jax.default_backend() == "tpu" else 16)
    max_seq_len = max(max_seq_len, 4 * page_size)
    cfg, model, params = built or _build(arch, smoke)

    rng0 = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(rng0.exponential(
        scale=mean_gap_steps, size=n_requests))).astype(int)
    specs = [(rng0.integers(0, cfg.vocab_size,
                            size=int(rng0.integers(3, max_seq_len // 3))),
              int(rng0.integers(4, 10))) for _ in range(n_requests)]

    def drive(metrics_on: bool):
        serve = ServeConfig(max_batch=max_batch, max_seq_len=max_seq_len,
                            page_size=page_size,
                            num_pages=max_batch * 3 + 1,
                            metrics=metrics_on)
        core = EngineCore(model, params, cfg, serve)
        rng = np.random.default_rng(seed + 1)
        wid = 0
        for w in (1, 2, max_batch):       # compile every launch width
            for i in range(w):
                wid -= 1
                core.add_request(rng.integers(0, cfg.vocab_size, size=3 + i),
                                 SamplingParams(max_new_tokens=2),
                                 request_id=wid)
            while core.has_work:
                core.step()
        core.reset()
        if metrics_on:
            core.reset_metrics_window()
        best = None
        for rep in range(repeats):        # identical arrival schedule
            next_req, step_idx, steps = 0, 0, 0
            t0 = time.perf_counter()
            while next_req < n_requests or core.has_work:
                while (next_req < n_requests
                       and arrivals[next_req] <= step_idx):
                    prompt, n = specs[next_req]
                    core.add_request(prompt,
                                     SamplingParams(max_new_tokens=n),
                                     request_id=1000 * rep + next_req)
                    next_req += 1
                core.step()
                steps += 1
                step_idx += 1
            dt = time.perf_counter() - t0
            assert core.mgr.used_pages == 0, "pages leaked after drain"
            ms = 1e3 * dt / steps
            best = ms if best is None else min(best, ms)
        return best, core

    # off first, on second: any in-process cache the second run could
    # inherit biases *against* finding overhead in the on run -- i.e.
    # keeps the CI gate conservative and stable
    off_ms, _ = drive(False)
    on_ms, core = drive(True)

    out = {
        "requests": n_requests,
        "repeats": repeats,
        "metrics_on_ms_per_step": round(on_ms, 2),
        "metrics_off_ms_per_step": round(off_ms, 2),
        "overhead_ratio": round(on_ms / off_ms, 3),
        "open_spans_after_drain": core.tracer.open_span_count(),
        "flight_records": len(core.flight.records),
    }
    if trace_out:
        trace = core.chrome_trace()
        with open(trace_out, "w") as f:
            json.dump(trace, f)
            f.write("\n")
        out["trace_events"] = len(trace["traceEvents"])
        out["trace_file"] = os.path.basename(trace_out)
    return out


def speculation(arch: str = "gemma2-2b", n_requests: int = 6,
                max_batch: int = 3, page_size: int = 0,
                spec_tokens: int = 4, gen_tokens: int = 24,
                repeats: int = 3, seed: int = 0, smoke: bool = True,
                built=None) -> dict:
    """Speculative decoding on a lookup-friendly workload: repetitive
    (tiled-motif) greedy prompts through the engine with prompt-lookup
    drafting on vs off.  Reports the accept rate, per-token time both
    ways (same tokens -- ``tokens_match`` asserts the greedy
    bit-identity contract), and ``off_step_time_ratio``: two *identical*
    spec-off engines timed interleaved, best-of-``repeats`` each -- the
    off path shares no code with speculation beyond a per-step ``is
    None`` branch, so CI gates the ratio at 1.02 (measurement noise)."""
    page_size = page_size or (
        128 if jax.default_backend() == "tpu" else 16)
    cfg, model, params = built or _build(arch, smoke)

    rng = np.random.default_rng(seed)
    prompts = []
    for i in range(n_requests):
        motif = rng.integers(1, cfg.vocab_size, size=5 + i % 3).tolist()
        n = 24 + 4 * (i % 4)
        prompts.append(np.array((motif * (n // len(motif) + 1))[:n],
                                np.int32))
    max_seq_len = max(p.size for p in prompts) + gen_tokens + page_size
    base = ServeConfig(max_batch=max_batch, max_seq_len=max_seq_len,
                       page_size=page_size)

    def drive(core, rep):
        for i, p in enumerate(prompts):
            core.add_request(p, SamplingParams(max_new_tokens=gen_tokens),
                             request_id=1000 * rep + i)
        toks = {i: [] for i in range(n_requests)}
        steps0, t0 = core.steps, time.perf_counter()
        while core.has_work:
            for ev in core.step():
                if ev.kind == "token":
                    toks[ev.request_id % 1000].append(ev.token)
        dt = time.perf_counter() - t0
        assert core.mgr.used_pages == 0, "pages leaked after drain"
        return toks, dt, core.steps - steps0

    def timed(core):
        """Warm (compile), then best-of-``repeats`` full drains.  The
        greedy reps are identical, so per-rep spec counters are just
        the timed totals divided by ``repeats``."""
        drive(core, 0)
        core.reset()
        core.reset_metrics_window()
        launch0, best = core.spec_launches, None
        for rep in range(1, repeats + 1):
            toks, dt, steps = drive(core, rep)
            core.reset()
            if best is None or dt < best[1]:
                best = (toks, dt, steps)
        return best + ((core.spec_launches - launch0) // repeats,)

    core_off = EngineCore(model, params, cfg, base)
    core_on = EngineCore(model, params, cfg, dataclasses.replace(
        base, spec_mode="lookup", spec_tokens=spec_tokens))
    off_toks, off_dt, off_steps, _ = timed(core_off)
    on_toks, on_dt, on_steps, on_launches = timed(core_on)
    sp = core_on.stats()["spec"]         # windows cover the timed reps

    # off-mode overhead: two identical spec-off engines, interleaved
    # best-of-``repeats`` -- any ratio above noise would mean the off
    # path is paying for a feature it never runs
    core_a = EngineCore(model, params, cfg, base)
    core_b = EngineCore(model, params, cfg, base)
    for c in (core_a, core_b):
        drive(c, 0)
        c.reset()
    best_a = best_b = None
    for rep in range(1, repeats + 1):
        _, dt_a, _ = drive(core_a, rep)
        core_a.reset()
        _, dt_b, _ = drive(core_b, rep)
        core_b.reset()
        best_a = dt_a if best_a is None else min(best_a, dt_a)
        best_b = dt_b if best_b is None else min(best_b, dt_b)

    n_gen = sum(len(t) for t in off_toks.values())
    return {
        "requests": n_requests,
        "spec_tokens": spec_tokens,
        "generated_tokens": n_gen,
        "tokens_match": bool(on_toks == off_toks),
        "drafted": sp["drafted"] // repeats,
        "accepted": sp["accepted"] // repeats,
        "accept_rate": round(sp["accept_rate"], 3),
        "off": {
            "ms_per_step": round(1e3 * off_dt / off_steps, 2),
            "tpot_ms": round(1e3 * off_dt / n_gen, 2),
            "engine_steps": off_steps,
        },
        "on": {
            "ms_per_step": round(1e3 * on_dt / on_steps, 2),
            "tpot_ms": round(1e3 * on_dt / n_gen, 2),
            "engine_steps": on_steps,
            "verify_launches": on_launches,
        },
        "tpot_speedup": round(off_dt / on_dt, 3),
        "off_step_time_ratio": round(best_b / best_a, 3),
    }


def _distributed_child(arch: str, n_requests: int, seed: int,
                       smoke: bool = True) -> None:
    """Runs INSIDE the forced-multi-device child process: tp=1 oracle,
    tp=2 / tp=4 tiled and tp=4 single-AllReduce runs of one workload;
    prints the section JSON on the last stdout line."""
    cfg, model, params = _build(arch, smoke)
    rng = np.random.default_rng(seed)
    prompts = []
    for i in range(n_requests):
        s = int(rng.integers(4, 40))
        prompts.append(rng.integers(0, cfg.vocab_size, size=s))
    max_new = 16

    def run_tp(tp, collectives="tiled"):
        serve = ServeConfig(max_batch=4, max_seq_len=96, page_size=16,
                            prefill_chunk=16, tp=tp,
                            tp_collectives=collectives)
        core = EngineCore(model=model, params=params, cfg=cfg, serve=serve)

        def drain(offset):
            toks = {}
            while core.has_work:
                for ev in core.step():
                    toks.setdefault(ev.request_id - offset,
                                    []).append(ev.token)
            return toks

        # pass 0 compiles (prefill widths + fused decode); pass 1 is the
        # timed, steady-state measurement on the same jit caches
        for i, p in enumerate(prompts):
            core.add_request(p, SamplingParams(max_new_tokens=max_new),
                             request_id=i)
        toks = drain(0)
        for i, p in enumerate(prompts):
            core.add_request(p, SamplingParams(max_new_tokens=max_new),
                             request_id=1000 + i)
        steps0 = core.stats()["steps"]
        t0 = time.perf_counter()
        timed = drain(1000)
        dt = time.perf_counter() - t0
        steps = core.stats()["steps"] - steps0
        assert timed == toks, "engine output changed between passes"
        total = sum(len(v) for v in timed.values())
        return toks, {
            "wall_s": round(dt, 3),
            "engine_steps": steps,
            "ms_per_step": round(1e3 * dt / steps, 2),
            "tokens_per_s": round(total / dt, 1),
        }

    base, t1 = run_tp(1)
    report = {
        "devices": jax.device_count(),
        "requests": n_requests,
        "generated_tokens": n_requests * max_new,
        "tokens_match": {},
        "tp1": t1,
    }
    for tp, coll in ((2, "tiled"), (4, "tiled"), (4, "single")):
        toks, timing = run_tp(tp, coll)
        report["tokens_match"][f"tp{tp}-{coll}"] = bool(toks == base)
        report[f"tp{tp}-{coll}"] = timing
    report["tp4_tiled_vs_single_step_speedup"] = round(
        report["tp4-single"]["ms_per_step"]
        / report["tp4-tiled"]["ms_per_step"], 3)
    print(json.dumps(report))


def distributed(arch: str = "gemma2-2b", n_requests: int = 6,
                devices: int = 4, seed: int = 0,
                smoke: bool = True) -> dict:
    """Tensor-parallel serving section: spawns a child process with
    ``devices`` forced fake CPU devices (the main process keeps its
    single-device view) and collects its report."""
    # the child's devices are fake CPU ones: on a TPU host it must not
    # try to take the chip this process already holds
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(REPO_ROOT, "src"), REPO_ROOT]))
    code = (f"from benchmarks.serving_bench import _distributed_child; "
            f"_distributed_child({arch!r}, {n_requests}, {seed}, {smoke})")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=REPO_ROOT, capture_output=True, text=True,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(
            f"distributed bench child failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=0,
                    help="0 = auto (128 on TPU, 16 on CPU smoke)")
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--pool-frac", type=float, default=0.6,
                    help="pool size as a fraction of the dense cache")
    ap.add_argument("--prefill-len", type=int, default=512,
                    help="prompt length for the prefill section")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunk size (0 = auto: 4 pages)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-smoke) model config")
    ap.add_argument("--skip-prefill", action="store_true",
                    help="skip the scan-vs-chunked prefill section")
    ap.add_argument("--skip-oversub", action="store_true",
                    help="skip the over-subscription section")
    ap.add_argument("--oversub-requests", type=int, default=8)
    ap.add_argument("--skip-prefix", action="store_true",
                    help="skip the prefix-sharing section")
    ap.add_argument("--prefix-requests", type=int, default=6)
    ap.add_argument("--skip-open-loop", action="store_true",
                    help="skip the open-loop EngineCore section")
    ap.add_argument("--open-loop-requests", type=int, default=10)
    ap.add_argument("--skip-degradation", action="store_true",
                    help="skip the load-shedding degradation section")
    ap.add_argument("--degradation-requests", type=int, default=14)
    ap.add_argument("--deadline-ms", type=float, default=1000.0,
                    help="per-request deadline in the bounded run")
    ap.add_argument("--max-waiting", type=int, default=2,
                    help="waiting-queue bound in the bounded run")
    ap.add_argument("--skip-observability", action="store_true",
                    help="skip the telemetry-overhead section")
    ap.add_argument("--observability-requests", type=int, default=10)
    ap.add_argument("--trace-out", default=os.path.join(
        REPO_ROOT, "BENCH_serving_trace.json"),
        help="flight-recorder Chrome trace artifact path ('' = skip)")
    ap.add_argument("--skip-speculation", action="store_true",
                    help="skip the speculative-decoding section")
    ap.add_argument("--speculation-requests", type=int, default=6)
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="max draft tokens per request per step")
    ap.add_argument("--skip-distributed", action="store_true",
                    help="skip the tensor-parallel serving section")
    ap.add_argument("--distributed-requests", type=int, default=6)
    ap.add_argument("--tp-devices", type=int, default=4,
                    help="forced fake CPU devices for the TP child")
    ap.add_argument("--mean-gap-steps", type=float, default=2.0,
                    help="mean Poisson inter-arrival gap (engine steps)")
    ap.add_argument("--system-len", type=int, default=96,
                    help="shared system-prompt length (prefix section)")
    ap.add_argument("--preempt-policy", default="swap",
                    choices=["auto", "swap", "recompute"])
    ap.add_argument("--json-out", default=os.path.join(
        REPO_ROOT, "BENCH_serving.json"))
    args = ap.parse_args()

    report = {
        "meta": {
            "arch": args.arch,
            "smoke": not args.full,
            "backend": jax.default_backend(),
            "paged_impl": ("paged" if jax.default_backend() == "tpu"
                           else "paged_reference"),
        },
        # tiny unit-test config: exercises slot/page churn
        "mixed_traffic": run(
            arch=args.arch, n_requests=args.requests,
            max_batch=args.max_batch, page_size=args.page_size,
            max_seq_len=args.max_seq_len, pool_frac=args.pool_frac,
            seed=args.seed, smoke=not args.full),
    }
    if not args.skip_prefill:
        # 'small' config: wide enough that prefill batching shows
        report["prefill"] = prefill_bench(
            arch=args.arch, prompt_len=args.prefill_len,
            page_size=args.page_size, prefill_chunk=args.prefill_chunk,
            seed=args.seed, smoke=not args.full)
    if not args.skip_oversub:
        # pool at pool_frac of realised worst-case demand: the pressure
        # subsystem (preempt + swap/recompute) absorbs the difference
        report["oversubscription"] = oversubscribe(
            arch=args.arch, n_requests=args.oversub_requests,
            page_size=args.page_size, pool_frac=args.pool_frac,
            preempt_policy=args.preempt_policy, seed=args.seed,
            smoke=not args.full)
    if not args.skip_prefix:
        # shared system prompt, cold vs warm: the radix prefix cache
        # must cut warm prefill work by >= the shared-prefix fraction
        report["prefix_sharing"] = prefix_sharing(
            arch=args.arch, n_requests=args.prefix_requests,
            system_len=args.system_len, page_size=args.page_size,
            seed=args.seed, smoke=not args.full)
    if not args.skip_open_loop:
        # requests arriving while the engine runs (EngineCore.step
        # driven directly): frontend-visible TTFT/TPOT percentiles
        report["open_loop"] = open_loop(
            arch=args.arch, n_requests=args.open_loop_requests,
            page_size=args.page_size,
            mean_gap_steps=args.mean_gap_steps, seed=args.seed,
            smoke=not args.full)
    if not args.skip_degradation:
        # over-offered load, unbounded vs deadline+shed bounded engine:
        # the survivors' latency tail must not regress under shedding
        report["degradation"] = degradation(
            arch=args.arch, n_requests=args.degradation_requests,
            page_size=args.page_size, deadline_ms=args.deadline_ms,
            max_waiting=args.max_waiting, seed=args.seed,
            smoke=not args.full)
    if not args.skip_observability:
        # metrics-on vs metrics-off step time on the open-loop workload:
        # telemetry must be free (host-side, between launches)
        report["observability"] = observability(
            arch=args.arch, n_requests=args.observability_requests,
            page_size=args.page_size,
            mean_gap_steps=args.mean_gap_steps, seed=args.seed,
            smoke=not args.full, trace_out=args.trace_out)
    if not args.skip_speculation:
        # prompt-lookup speculation on a repetitive greedy workload:
        # same tokens in fewer, fatter steps; off mode must stay free
        report["speculation"] = speculation(
            arch=args.arch, n_requests=args.speculation_requests,
            page_size=args.page_size, spec_tokens=args.spec_tokens,
            seed=args.seed, smoke=not args.full)
    if not args.skip_distributed:
        # tensor-parallel engine on a forced multi-device CPU mesh:
        # bit-identity vs tp=1 and tiled- vs single-AllReduce step time
        report["distributed"] = distributed(
            arch=args.arch, n_requests=args.distributed_requests,
            devices=args.tp_devices, seed=args.seed, smoke=not args.full)

    def flat(prefix, d):
        for k, v in d.items():
            if isinstance(v, dict):
                flat(f"{prefix}{k}.", v)
            else:
                print(f"{prefix}{k},{v}", flush=True)
    flat("", report)
    with open(args.json_out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.json_out}")


if __name__ == "__main__":
    main()
