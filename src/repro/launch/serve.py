"""Serving driver: batched generation with FastAttention (+T4 offload),
or the persistent paged EngineCore (``--stream``).

    # dense static-batch generation (the original path)
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
        --batch 4 --prompt-len 64 --gen 16

    # iteration-level serving: EngineCore.add_request/step with
    # per-request SamplingParams (every 3rd request samples, seeded)
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
        --stream --requests 8 --prompt-len 24 --gen 12
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, NamedTuple, Set

import jax
import numpy as np

from repro.config import (ParallelConfig, ServeConfig, get_model_config,
                          reduce_for_smoke)
from repro.core.offload import plan_offload
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for
from repro.models import build_model
from repro.serving.core import EngineCore
from repro.serving.engine import ServeEngine
from repro.serving.faults import RequestRejected
from repro.serving.scheduler import SamplingParams
from repro.sharding.rules import axis_rules


def init_params(model, seed: int, sharding=None):
    """Random weights from ``seed``, made on the device under jit.  The
    eager ``model.init`` stacks the per-layer trees and briefly holds two
    copies of the weights; the jitted one writes each leaf once.
    ``sharding`` places the tree (e.g. replicated over a TP mesh)."""
    return jax.jit(model.init, out_shardings=sharding)(
        jax.random.PRNGKey(seed))


class StreamResult(NamedTuple):
    tokens: Dict[int, List[int]]    # request id -> streamed tokens
    errors: Dict[int, str]          # request id -> error event detail
    finished: Set[int]              # ids whose terminal event arrived
    n_events: int                   # non-error events (tokens and stops)
    seconds: float                  # host wall time of the drain


def drain(core: EngineCore, log=print) -> StreamResult:
    """Step ``core`` until it has no work, collecting every request's
    stream and logging each request that finishes or fails."""
    tokens: Dict[int, List[int]] = {}
    errors: Dict[int, str] = {}
    finished: Set[int] = set()
    n_events = 0
    t0 = time.perf_counter()  # repro-lint: disable=raw-wall-clock (CLI wall time)
    while core.has_work:
        for ev in core.step():
            if ev.finished:
                finished.add(ev.request_id)
            if ev.kind == "error":
                errors[ev.request_id] = ev.detail
                log(f"req {ev.request_id} failed: {ev.detail}")
                continue
            n_events += 1
            if ev.kind == "token":
                tokens.setdefault(ev.request_id, []).append(ev.token)
            if ev.finished:
                log(f"req {ev.request_id} finished ({ev.index + 1} tokens)")
    dt = time.perf_counter() - t0  # repro-lint: disable=raw-wall-clock (CLI wall time)
    return StreamResult(tokens, errors, finished, n_events, dt)


def _run_stream(model, params, cfg, args) -> None:
    """Drive the persistent EngineCore directly: submit a queue of
    requests with mixed per-request SamplingParams, step the engine,
    and print tokens as they stream out."""
    page_size = 128 if jax.default_backend() == "tpu" else 16
    serve = ServeConfig(
        max_batch=min(4, args.requests),
        max_seq_len=args.prompt_len + args.gen + page_size,
        page_size=page_size,
        max_waiting=args.max_waiting,
        queue_policy=args.queue_policy,
        spec_mode=args.spec_mode,
        spec_tokens=args.spec_tokens if args.spec_tokens > 0 else 4)
    core = EngineCore(model, params, cfg, serve)
    rng = np.random.default_rng(0)
    # --top-k 1 (the dense-path greedy default) would make the "sampled"
    # requests greedy too; give them a real truncation instead
    stream_top_k = args.top_k if args.top_k not in (0, 1) else 8
    deadline = args.deadline_ms if args.deadline_ms > 0 else None
    for i in range(args.requests):
        if i % 3 == 2:
            sp = SamplingParams(temperature=0.8, top_k=stream_top_k,
                                seed=i, max_new_tokens=args.gen,
                                deadline_ms=deadline)
        else:
            sp = SamplingParams(max_new_tokens=args.gen,
                                deadline_ms=deadline)   # greedy
        if args.spec_mode != "off":
            # prompt-lookup thrives on repetitive text; tile a short
            # motif so the demo shows a real accept rate
            motif = rng.integers(1, cfg.vocab_size, size=7).tolist()
            prompt = np.array(
                (motif * (args.prompt_len // 7 + 1))[:args.prompt_len],
                np.int32)
        else:
            prompt = rng.integers(0, cfg.vocab_size, size=args.prompt_len)
        try:
            core.add_request(prompt, sp)
        except RequestRejected as e:
            # queue_policy="reject" surfaces a structured error at
            # submission; the engine keeps serving what it admitted
            print(f"rejected: {e.detail}")
    res = drain(core)
    n_events, dt = res.n_events, res.seconds
    s = core.stats()
    print(f"{n_events} tokens in {dt:.2f}s ({n_events / dt:.1f} tok/s), "
          f"{s['steps']} engine steps, peak pool "
          f"{s['pages_peak']}/{core.mgr.usable_pages} pages "
          f"({s['peak_utilization']:.0%}), "
          f"{s['pressure']['preemptions']} preemptions")
    # health printout sourced from the registry snapshot (the same
    # numbers stats()["health"] mirrors -- counters read their windows)
    snap = core.metrics.snapshot()

    def _w(name):
        m = snap.get(name)
        return m["window"] if m else 0

    hw = snap.get("engine_step_seconds", {}).get("max", 0.0)
    print(f"health: {_w('engine_requests_failed_total')} failed, "
          f"{_w('engine_requests_shed_total')} shed, "
          f"{_w('engine_requests_timed_out_total')} timed out, "
          f"{_w('pressure_swap_retries_total')} swap retries "
          f"({_w('pressure_swap_fail_downgrades_total')} downgraded to "
          f"recompute), slowest step {hw * 1e3:.1f}ms"
          + (f", last error: {s['health']['last_error']}"
             if s["health"]["last_error"] else ""))
    if "spec" in s:
        sp = s["spec"]
        print(f"speculation: {sp['accepted']}/{sp['drafted']} drafts "
              f"accepted ({sp['accept_rate']:.0%}) over "
              f"{sp['verify_launches']} verify launches")
    if core.tracer is not None and core.tracer.completed:
        ttfts = sorted(r["first_token_t"] - r["submit_t"]
                       for r in core.tracer.completed
                       if r["first_token_t"] is not None)
        if ttfts:
            print(f"engine-native TTFT: p50 "
                  f"{ttfts[len(ttfts) // 2] * 1e3:.1f}ms, max "
                  f"{ttfts[-1] * 1e3:.1f}ms over {len(ttfts)} requests")
    if args.metrics is not None:
        print("---- prometheus " + "-" * 48)
        print(core.export_prometheus(), end="")
        if args.metrics != "-":
            import json
            with open(args.metrics, "w") as f:
                json.dump(core.chrome_trace(), f)
            print(f"---- chrome trace ({len(core.flight.records)} steps) "
                  f"written to {args.metrics}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--top-k", type=int, default=1)
    ap.add_argument("--offload-report", action="store_true")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the paged EngineCore "
                         "(add_request/step) instead of dense generate")
    ap.add_argument("--requests", type=int, default=8,
                    help="requests to stream (with --stream)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline in ms (0 = none; expired "
                         "requests are shed with a structured timeout)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="bound on the waiting queue (0 = unbounded)")
    ap.add_argument("--queue-policy", default="reject",
                    choices=["reject", "shed_oldest"],
                    help="full-queue policy: reject new arrivals or "
                         "shed the oldest waiting request")
    ap.add_argument("--spec-mode", default="off",
                    choices=["off", "lookup"],
                    help="with --stream: speculative decoding drafter "
                         "(lookup = prompt-lookup n-gram matching; "
                         "greedy output is bit-identical either way)")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="max draft tokens per request per step "
                         "(0 = engine default of 4; only with "
                         "--spec-mode lookup)")
    ap.add_argument("--metrics", nargs="?", const="-", default=None,
                    metavar="TRACE_JSON",
                    help="with --stream: print the Prometheus text "
                         "exposition at end of run; with a path, also "
                         "write the flight recorder's Chrome trace_event "
                         "JSON there (load in chrome://tracing)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_model_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    parallel = ParallelConfig()
    mesh = make_mesh_for(parallel)
    model = build_model(cfg, parallel)

    if args.offload_report:
        plan = plan_offload(cfg, batch=args.batch,
                            seq_len=args.prompt_len + args.gen,
                            gen_len=args.gen, n_devices=1)
        print("T4 offload plan:", plan.summary())

    with axis_rules(mesh=mesh):
        params = init_params(model, 0)
        if args.stream:
            _run_stream(model, params, cfg, args)
            return
        serve = ServeConfig(max_seq_len=args.prompt_len + args.gen + 1,
                            top_k=args.top_k)
        engine = ServeEngine(model=model, params=params, cfg=cfg,
                             serve=serve)
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
        t0 = time.perf_counter()  # repro-lint: disable=raw-wall-clock (CLI wall time)
        out = engine.generate(tokens, args.gen)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0  # repro-lint: disable=raw-wall-clock (CLI wall time)
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s)")
        print("sample:", out[0, :16].tolist())


if __name__ == "__main__":
    main()
