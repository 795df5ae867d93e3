#!/usr/bin/env python3
"""Serving benchmark: EngineCore on the chip, one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process is one run.  It reads the cell from ``BENCHMARK.json`` at the
root of the checkout, its configuration file and its traffic mix
(``bench/traffic/<mix>.json``, made by ``bench/traffic/gen_<kind>.py``),
then:

1. refuses to run (exit 2, no result) without a TPU, with fewer chips
   than the cell asks for, or on a device kind missing from
   ``bench/peaks.json``;
2. builds the model at its published widths with random bfloat16
   weights made on the device from ``--seed`` (``weights.py``), the
   engine with the mix's ``engine`` settings, and warms every program
   the traffic can launch (``system.warm``); the persistent compilation
   cache lives in ``.jax_cache/`` of the checkout.  Everything up to
   here is ``setup_s``;
3. drives ``EngineCore.add_request``/``step`` from the mix's schedule
   for ``--seconds``: requests are submitted when due (open loop) and
   timed from when they were due; with ``--trace 1`` the middle of the
   window (at most ``TRACE_S`` seconds) runs under ``jax.profiler``;
4. where the mix asks for it, keeps stepping past the close until every
   request due in the window has its first token;
5. reads the peak device memory, frees the engine, and checks a sample
   of the finished greedy requests against the float32 reference
   (``reference.py`` around the layer of the configuration's model
   family, ``bench/families/<reference>.py``) within the cell's limits
   (``bench/checks/<cell>.json``);
6. prints the generator's lateness and the compilations inside the
   window, the compared numbers beside their limits (as the last lines
   of standard error), and as the last line of standard output one JSON
   object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
   cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
   ``device``, with ``--trace 1`` ``breakdown``, and ``check``.

Each metric is computed by ``bench/metrics/<name>.py`` from the run's
records (``Run``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from manifest import Manifest, family_of  # noqa: E402

TRACE_S = 10.0          # longest traced stretch of a window
DRAIN_S = 60.0          # longest wait past the close for first tokens
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (the union of
    those spans), and the time of each lowering: a program lowered
    inside the window is a compilation inside it."""

    def __init__(self, jax):
        self.spans: List[tuple] = []
        self.lowered: List[float] = []
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    @property
    def seconds(self) -> float:
        total, end = 0.0, float("-inf")
        for s, e in sorted(self.spans):
            if e > end:
                total += e - max(s, end)
                end = e
        return total

    def _span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))
        if event == LOWERING:
            self.lowered.append(end)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lowered_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.lowered if t0 <= t <= t1)


@dataclasses.dataclass
class Rec:
    """One request as the client sees it (host perf_counter seconds)."""
    due: float
    prompt: list
    max_new_tokens: int
    greedy: bool
    submitted: float = math.nan
    first: float = math.nan
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    error: str = ""


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cfg: dict
    mix: dict
    cell: dict
    peaks: dict
    chips: int
    t0: float                       # window opens
    t1: float                       # window closes
    recs: Dict[int, Rec]
    setup_s: float
    counters: Dict[str, float]      # engine registry, window values
    usable_pages: int
    launches: Optional[List[dict]] = None   # --trace 1: each step's work
    trace: object = None                    # --trace 1: trace_reduce.Reduced
    trace_t0: float = math.nan              # traced stretch, host clock
    trace_t1: float = math.nan
    decode_kernel: str = ""
    prefill_kernel: str = ""
    family: object = None                   # bench/families/<reference>.py


def sampling_params(spec):
    from repro.serving.scheduler import SamplingParams
    s = spec["sampling"]
    if s is None:
        return SamplingParams(max_new_tokens=spec["max_new_tokens"])
    return SamplingParams(temperature=s["temperature"], top_k=s["top_k"],
                          seed=s["seed"],
                          max_new_tokens=spec["max_new_tokens"])


class WindowTrace:
    """``jax.profiler`` over the middle ``TRACE_S`` seconds of a window,
    inside a host span named ``window``."""

    def __init__(self, log_dir: str, seconds: float, clock):
        span = min(TRACE_S, seconds)
        self.start = (seconds - span) / 2
        self.stop = self.start + span
        self.log_dir, self.clock = log_dir, clock
        self.t0 = self.t1 = math.nan
        self._span = None

    def tick(self, elapsed: float) -> None:
        import jax
        if self._span is None and math.isnan(self.t0) \
                and elapsed >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("window")
            self._span.__enter__()
            self.t0 = self.clock()
        elif self._span is not None and elapsed >= self.stop:
            self.close()

    def close(self) -> None:
        import jax
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self.t1 = self.clock()
            jax.profiler.stop_trace()
            self._span = None


def drive(core, schedule: List[dict], seconds: float, trace_dir, clock,
          log=print, launches: Optional[List[dict]] = None) -> dict:
    """Submit each request when due and step the engine for ``seconds``;
    returns the records and the window's bounds.  A request the engine
    refuses at submission is kept as a record with the error
    ``rejected``: it never gets a token.  With ``launches`` (a list),
    each step's work is appended to it (``step_work``)."""
    import jax
    from repro.serving.faults import RequestRejected
    recs: Dict[int, Rec] = {}
    pending = deque(schedule)
    rejected = 0
    lateness: List[float] = []
    tracer = WindowTrace(trace_dir, seconds, clock) if trace_dir else None
    step_no = 0
    core.reset_metrics_window()
    t0 = clock()

    def submit_due(now):
        nonlocal rejected
        while pending and t0 + pending[0]["due"] <= now:
            spec = pending.popleft()
            with jax.profiler.TraceAnnotation("submit"):
                due = t0 + spec["due"]
                try:
                    rid = core.add_request(spec["prompt"],
                                           sampling_params(spec))
                except RequestRejected as e:
                    rejected += 1
                    log(f"rejected at submit: {e}")
                    recs[-rejected] = Rec(
                        due=due, prompt=spec["prompt"].tolist(),
                        max_new_tokens=spec["max_new_tokens"],
                        greedy=spec["sampling"] is None, error="rejected")
                    continue
                t = clock()
                lateness.append(t - due)
                recs[rid] = Rec(due=due, prompt=spec["prompt"].tolist(),
                                max_new_tokens=spec["max_new_tokens"],
                                greedy=spec["sampling"] is None,
                                submitted=t)

    def step():
        nonlocal step_no
        before = None if launches is None else prefill_progress(core, recs)
        with jax.profiler.StepTraceAnnotation("step", step_num=step_no):
            events = core.step()
        step_no += 1
        t = clock()
        decode_ctx = []
        for ev in events:
            r = recs.get(ev.request_id)
            if r is None:
                continue
            if ev.kind == "error":
                r.error = ev.detail or "error"
            elif ev.kind == "token":
                if not r.times:
                    r.first = t
                else:       # a decode row: it saw the prompt and the tokens
                    decode_ctx.append(len(r.prompt) + len(r.tokens))
                r.times.append(t)
                r.tokens.append(ev.token)
            if ev.finished:
                r.finished = True
        if launches is not None:
            launches.extend(step_work(core, before, decode_ctx, t))

    while True:
        now = clock()
        if now - t0 >= seconds:
            break
        if tracer is not None:
            tracer.tick(now - t0)
        submit_due(now)
        if core.has_work:
            step()
        else:
            wake = t0 + min(pending[0]["due"] if pending else seconds,
                            seconds)
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(max(0.0, wake - clock()))
    t1 = clock()
    if tracer is not None:
        tracer.close()
    return {"recs": recs, "t0": t0, "t1": t1, "rejected": rejected,
            "lateness": lateness, "step": step, "steps": step_no,
            "tr_t0": tracer.t0 if tracer else math.nan,
            "tr_t1": tracer.t1 if tracer else math.nan}


def prefill_progress(core, recs: Dict[int, Rec]) -> Dict[int, int]:
    """Prompt tokens already in the cache of each request that has no
    token yet (``EngineCore.get_request``)."""
    out = {}
    for rid, r in recs.items():
        if r.times or r.error:
            continue
        req = core.get_request(rid)
        if req is not None:
            out[rid] = req.prefilled
    return out


def step_work(core, before: Dict[int, int], decode_ctx: List[int],
              t: float) -> List[dict]:
    """One step's launches as the client and the engine's public request
    state show them: the decode rows (the positions each saw) and the
    prefill rows ``(start, tokens)``; a prefill row starts past a prefix
    hit (``matched_len``)."""
    out = []
    rows = []
    for rid, done in before.items():
        req = core.get_request(rid)
        if req is None:
            continue
        start = max(done, req.matched_len)
        if req.prefilled > start:
            rows.append((start, req.prefilled - start))
    if rows:
        out.append({"kind": "prefill", "t": t, "rows": rows})
    if decode_ctx:
        out.append({"kind": "decode", "t": t, "ctx": decode_ctx})
    return out


def drain_first_tokens(core, out: dict, clock) -> float:
    """Step on (no new arrivals) until every request due in the window
    has its first token, failed, or ``DRAIN_S`` passed; returns the
    seconds spent."""
    import jax
    recs, t1 = out["recs"], out["t1"]
    start = clock()
    with jax.profiler.TraceAnnotation("drain"):
        while core.has_work and clock() - start < DRAIN_S and any(
                math.isnan(r.first) and not r.error
                for r in recs.values() if r.due <= t1):
            out["step"]()
    return clock() - start


def prepare(cfg: dict, mix: dict, seed: int, gen, marks: list,
            break_engine=None):
    """Set-up: the engine with the seed's weights, every program warmed,
    the schedule, and the set-up traffic the mix needs in the cache.
    Appends ``(phase, time)`` to ``marks``."""
    import system
    clock = time.perf_counter
    core = system.build(cfg, mix["engine"], seed)
    marks.append(("weights and engine", clock()))
    if break_engine is not None:
        break_engine(core)
    samplings = [{"temperature": mix["temperature"], "top_k": mix["top_k"]}
                 ] if mix.get("sampled_every") else []
    eng = mix["engine"]
    system.warm(core, samplings, cow_pairs=eng.get("cow_pairs", 0),
                max_width=eng.get("max_prefill_width", 0))
    marks.append(("warm-up", clock()))
    schedule = gen.generate(mix, cfg, seed)
    marks.append(("schedule", clock()))
    for spec in mix.get("setup_requests", []):
        # set-up traffic the cell needs in the cache (shared prompts)
        for prompt in gen.setup_prompts(mix, cfg, seed, spec):
            core.add_request(prompt, sampling_params(
                {"sampling": None, "max_new_tokens": spec["max_new_tokens"]}))
        while core.has_work:
            core.step()
        marks.append((f"set-up traffic {spec['what']}", clock()))
    return core, schedule


def measure(core, schedule: List[dict], mix: dict, seconds: float,
            trace_dir, clock, launches: Optional[List[dict]] = None) -> dict:
    """The window, then the drain the mix asks for."""
    out = drive(core, schedule, seconds, trace_dir, clock,
                log=lambda m: print(m, file=sys.stderr), launches=launches)
    out["drain_s"] = 0.0
    if mix.get("drain") == "first_token":
        out["drain_s"] = drain_first_tokens(core, out, clock)
    del out["step"]                # its closure holds the engine
    return out


def choose_sample(recs: Dict[int, Rec], spec: dict, seed: int) -> List[int]:
    """Finished greedy requests to check: the one with the longest
    context, then others drawn from the seed until ``min_tokens`` served
    tokens or ``max_requests`` requests."""
    import numpy as np
    done = sorted(rid for rid, r in recs.items()
                  if r.finished and not r.error and r.greedy and r.tokens)
    if not done:
        return []
    longest = max(done, key=lambda i: len(recs[i].prompt)
                  + len(recs[i].tokens))
    rest = [i for i in done if i != longest]
    np.random.default_rng([seed, 7]).shuffle(rest)
    pick, n = [longest], len(recs[longest].tokens)
    for i in rest:
        if n >= spec["min_tokens"] or len(pick) >= spec["max_requests"]:
            break
        pick.append(i)
        n += len(recs[i].tokens)
    return pick


def gap_numbers(gs) -> dict:
    """The widest and the mean gap of the compared tokens, and the share
    of them that are not the reference's first choice."""
    import numpy as np
    gs = np.concatenate(gs) if gs else np.zeros(0)
    if not len(gs):
        return {"logit_gap": math.inf, "mean_logit_gap": math.inf,
                "flip_share": math.inf}
    return {"logit_gap": float(gs.max()), "mean_logit_gap": float(gs.mean()),
            "flip_share": float(np.mean(gs > 0))}


def check(cfg: dict, seed: int, max_len: int, recs: Dict[int, Rec],
          spec: dict, control: Optional[str] = None) -> dict:
    """The compared numbers: how far the served greedy tokens' float32
    reference logits lie below the reference's best (``gap_numbers``),
    on a sample of finished requests; requests ended by an error or
    refused; finished requests short of their ``max_new_tokens``.  With
    ``control`` (a kind of ``reference.QUANT``) also, under
    ``"control"``, the gap numbers of the first choices of the reference
    computed with its weights in that precision, at each position of the
    same prompts and tokens."""
    import numpy as np
    from reference import Reference, gaps
    pick = choose_sample(recs, spec, seed)
    out = {"sample_requests": len(pick),
           "sample_tokens": sum(len(recs[i].tokens) for i in pick),
           "errors": sum(1 for r in recs.values() if r.error),
           "short": sum(1 for r in recs.values() if r.finished
                        and not r.error
                        and len(r.tokens) != r.max_new_tokens)}
    rows = []
    if pick:
        ref = Reference(cfg, seed, max_len, control=control)
        rows = ref.logits([(recs[i].prompt + recs[i].tokens,
                            len(recs[i].prompt) - 1, len(recs[i].tokens))
                           for i in pick])
    out.update(gap_numbers([gaps(r["ref"], recs[i].tokens)
                            for r, i in zip(rows, pick)]))
    if control:
        out["control"] = gap_numbers(
            [gaps(r["ref"], np.argmax(r[control], axis=-1)) for r in rows])
    return out


def compare(numbers: dict, limits: dict) -> Tuple[dict, bool]:
    """Each compared number beside its limit (the ``max`` of the cell's
    ``bench/checks/<cell>.json``; no request ended by an error or
    refused; none finished short; at least ``least_tokens`` tokens
    compared), and whether all of them hold."""
    compared = {k: {"value": numbers[k], "limit": lim}
                for k, lim in limits["max"].items()}
    compared.update(
        errors={"value": numbers["errors"], "limit": 0},
        short={"value": numbers["short"], "limit": 0})
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    least = limits["sample"]["least_tokens"]
    compared["sample_tokens"] = {"value": numbers["sample_tokens"],
                                 "limit": least}
    return compared, bool(correct and numbers["sample_tokens"] >= least)


def control_numbers(numbers: dict) -> dict:
    """The numbers ``compare`` reads, with the control put in the
    program's place: its gaps, on the program's sample."""
    out = {k: v for k, v in numbers.items() if k != "control"}
    out.update(numbers["control"])
    return out


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         use_cache: bool = True, break_engine=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = T_START if root == ROOT else time.perf_counter()
    clock = time.perf_counter
    man = Manifest(root)
    cell = man.cell(args.workload)
    cfg = man.config(cell)
    family = family_of(cfg)
    mix = man.traffic(cell)
    with open(os.path.join(man.bench, "checks", f"{cell['name']}.json")) as f:
        limits = json.load(f)
    if use_cache:
        cache = os.path.join(root, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    if use_cache:
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    kind = devices[0].device_kind
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    peaks = man.peaks(kind)
    if peaks is None:
        if require_chip:
            print(f"bench: device kind {kind!r} is not in bench/peaks.json",
                  file=sys.stderr)
            return 2
        peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    clk = CompileClock(jax)
    marks = [("start", t_start), ("jax", clock())]
    import system
    gen = man.generator(mix["kind"])
    core, schedule = prepare(cfg, mix, args.seed, gen, marks, break_engine)
    launches = [] if args.trace else None
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    jax.block_until_ready(core.pools)
    setup_s = clock() - t_start
    compile_setup = clk.seconds
    print("set-up phases (s): " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.3f}" for a, b in zip(marks, marks[1:])))
    out = measure(core, schedule, mix, args.seconds, trace_dir, clock,
                  launches)
    drain_s = out["drain_s"]
    recs = out["recs"]
    in_window = clk.lowered_between(out["t0"], out["t1"])
    snap = core.metrics.snapshot()
    counters = {}
    for name, m in snap.items():
        counters[name] = m.get("window", m.get("value", 0.0))
    usable = core.mgr.usable_pages
    mem_peak = system.peak_bytes(devices[:cell["chips"]])
    del core
    gc.collect()
    held = max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    print(f"device bytes in use once the engine is freed: {held}")
    reduced = None
    if trace_dir is not None:
        import trace_reduce
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            reduced = trace_reduce.reduce_file(
                path, (system.DECODE_KERNEL, system.PREFILL_KERNEL)
                + tuple(getattr(family, "KERNELS", ())))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cfg=cfg, mix=mix, cell=cell, peaks=peaks, chips=cell["chips"],
              t0=out["t0"], t1=out["t1"], recs=recs, setup_s=setup_s,
              counters=counters, usable_pages=usable, launches=launches,
              trace=reduced, trace_t0=out["tr_t0"], trace_t1=out["tr_t1"],
              decode_kernel=system.DECODE_KERNEL,
              prefill_kernel=system.PREFILL_KERNEL, family=family)
    lat = sorted(out["lateness"])
    print(f"window {out['t1'] - out['t0']:.3f} s, {out['steps']} steps; "
          f"drain {drain_s:.3f} s; requests due {len(recs)}, "
          f"rejected {out['rejected']}; generator lateness max "
          f"{(lat[-1] if lat else 0.0) * 1e3:.3f} ms, median "
          f"{(lat[len(lat) // 2] if lat else 0.0) * 1e3:.3f} ms; "
          f"programs lowered inside the window {in_window}; set-up "
          f"{setup_s:.3f} s, of which compiling {compile_setup:.3f} s "
          f"({clk.cache_hits} persistent-cache hits)")
    kind_key = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in man.metrics_for(cell, kind_key):
        v = man.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if not args.trace:
        # the load a fixed-rate cell offers, for the record
        other = {m["name"]: man.reader(m["name"])(run)
                 for m in man.data["end_to_end"]
                 if m["name"] not in metrics and man.metric_path(m["name"])}
        print("other end-to-end readings: " + json.dumps(other))
    if mix.get("drain") == "first_token":
        from stats import percentile, ttft_ms
        xs = ttft_ms(recs.values(), out["t1"])
        if xs:
            print(f"ttft over {len(xs)} requests due in the window (ms, "
                  f"no bound): p50 {percentile(xs, 50)}, p90 "
                  f"{percentile(xs, 90)}")
    numbers = check(cfg, args.seed, mix["engine"]["max_seq_len"], recs,
                    limits["sample"])
    compared, correct = compare(numbers, limits)
    print("check readings: " + json.dumps(numbers))
    result = {
        "correct": bool(correct),
        "attempted": len(recs),
        "failed": numbers["errors"],
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": cell["chips"], "memory_peak_bytes": mem_peak},
    }
    if args.trace:
        if reduced is not None:
            result["device"]["busy_s"] = reduced.busy_mean_s
            result["device"]["window_s"] = reduced.window_s
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in reduced.top_ops],
                "idle_gaps": [[n, s] for n, s in reduced.idle_gaps]}
            print("idle by host span: " + json.dumps(reduced.idle_by_host))
    result["check"] = compared
    sys.stdout.flush()
    for k, v in compared.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
