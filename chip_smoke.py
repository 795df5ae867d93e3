#!/usr/bin/env python3
"""Bring-up smoke run: serve phi3-mini-3.8b on a TPU through EngineCore.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --tp 4      # tp=4 engine vs the tp=1 engine

The model is built at its published widths with random weights from
``--seed`` and served through ``EngineCore`` with the stream loop of
``python -m repro.launch.serve --stream`` and the compiled Pallas paged
kernels (pages of 128 tokens, 512-token prefill chunks).  Three waves of
requests drive the main path:

1. a prompt three prefill chunks long (greedy) and a seeded sampled one;
2. two requests sharing the first prompt's page-aligned 4-page prefix
   through the radix prefix cache: a full hit, whose recomputed last
   token copy-on-writes the shared tail page, and a partial hit;
3. four requests whose decode growth overflows the small page pool, so
   at least one is preempted, swapped to the host and swapped back.

Every request must finish with no error event, no failed request and no
swap downgraded to recompute.  On one chip, the logits each request's
first token was sampled from must agree with ``LM.apply(impl=
"reference")`` on the same prompt within ``LOGIT_TOL``, and every greedy
token must be a top-1 choice of that reference forward on the same
tokens (ties within twice the tolerance allowed).  With ``--tp N`` the
same waves run through a tp=N engine and the tp=1 engine only, and their
first-token logits and greedy streams are compared.

Without a TPU it exits 2 before serving.  A failed check exits 1.  Only
a run that passes prints, as its last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Dict, List

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.config import ParallelConfig, ServeConfig, get_model_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import drain, init_params  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving.core import EngineCore  # noqa: E402
from repro.serving.scheduler import RUNNING, SamplingParams  # noqa: E402
from repro.sharding.tp import plan_tp  # noqa: E402

ARCH = "phi3-mini-3.8b"
PAGE = 128

# Largest |engine - reference| logit difference allowed, as a fraction of
# the row's largest |logit|.  The engine computes in bf16, which keeps 8
# mantissa bits (a rounding error of 2^-9 of a value), and rounds the
# residual stream, the attention output and the MLP output of each of
# the 32 layers; the drift this leaves grows slowly with depth.  At full
# width on the CPU (jnp paged path against the float32 reference) it
# was 1.5% with 2 layers and 1.9% with 8.  2^-4 keeps room above that,
# and the same 2-layer run fails it when attention drops one KV row:
# the current token's in decode (18%) or the prompt's last in prefill
# (11%).
LOGIT_TOL = 2.0 ** -4

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling: the union of
    those spans (a nested jit's trace lies inside its caller's).  A hit
    in the persistent compilation cache shortens the compiling part."""

    def __init__(self):
        self.spans: List[tuple] = []
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

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def make_waves(vocab: int, ps: int, seed: int):
    """The three waves of ``(prompt, SamplingParams)``.  Lengths scale
    with the page size ``ps``, so a small page size replays the same
    page-level schedule (32 to 48 new tokens at ``ps=128``)."""
    rng = np.random.default_rng(seed)

    def tokens(n):
        return rng.integers(0, vocab, size=n).tolist()

    gen = SamplingParams(max_new_tokens=max(2, ps // 4))
    long = tokens(round(8.6 * ps))         # three 4-page prefill chunks
    prefix = long[:4 * ps]
    sampled = SamplingParams(temperature=0.8, top_k=8, seed=seed,
                             max_new_tokens=max(2, ps // 4))
    # wave 3: each prompt fills 6 pages but 2 rows, so its third decode
    # step takes a 7th page while later prompts of the wave still
    # prefill: 4 x 7 pages overflow the 26 usable pages of
    # serve_config(), and the newest sequence is swapped out
    pressure = SamplingParams(max_new_tokens=max(12, 3 * ps // 8))
    return [
        [(long, gen), (tokens(round(4.7 * ps)), sampled)],
        [(prefix, gen),                            # full hit -> COW
         (prefix + tokens(round(1.6 * ps)), gen)],  # partial hit
        [(tokens(6 * ps - 2), pressure) for _ in range(4)],
    ]


def serve_config(ps: int, tp: int = 1, paged_impl: str = "auto"):
    return ServeConfig(max_batch=4, max_seq_len=9 * ps, page_size=ps,
                       num_pages=27, prefix_cache=True,
                       preempt_policy="swap", tp=tp, paged_impl=paged_impl)


@dataclasses.dataclass
class Served:
    prompts: Dict[int, List[int]]
    greedy: Dict[int, bool]
    tokens: Dict[int, List[int]]
    errors: Dict[int, str]
    finished: set
    # request id -> {token index: logits row the token was picked from}
    logits: Dict[int, Dict[int, np.ndarray]]
    stats: dict
    counters: Dict[str, int]
    wall_s: float
    compile_s: float


def keep_logits(core: EngineCore) -> Dict[int, Dict[int, np.ndarray]]:
    """Record the logits row each token is picked from, by wrapping the
    core's first-token step and its jitted decode step.  Every RUNNING,
    unfinished slot is in the decode batch, and its row predicts the
    request's next token."""
    rows: Dict[int, Dict[int, np.ndarray]] = {}
    first_token, paged_fns = core._first_token, core._paged_fns

    def first(req, slot, last_logits, events):
        rows.setdefault(req.id, {})[0] = np.asarray(last_logits[0],
                                                    np.float32)
        first_token(req, slot, last_logits, events)

    def fns():
        pre_scan, pre_chunk, dec, verify = paged_fns()

        def decode(params, tok, pools, table, pos):
            logits, pools = dec(params, tok, pools, table, pos)
            host = np.asarray(logits, np.float32)
            for slot, req in enumerate(core.sched.slots):
                if req is not None and req.state == RUNNING \
                        and not req.done:
                    rows[req.id][len(req.generated)] = host[slot]
            return logits, pools

        return pre_scan, pre_chunk, decode, verify

    core._first_token, core._paged_fns = first, fns
    return rows


def serve_waves(model, params, cfg, serve: ServeConfig, waves,
                clock: CompileClock, log=print) -> Served:
    """Submit each wave to one EngineCore and drain it before the next
    (a retiring request publishes its prefix for the next wave)."""
    core = EngineCore(model, params, cfg, serve)
    logits = keep_logits(core)
    prompts, greedy, tokens, errors, finished = {}, {}, {}, {}, set()
    c0 = clock.seconds
    t0 = time.perf_counter()
    for wave in waves:
        for prompt, sp in wave:
            rid = core.add_request(np.asarray(prompt, np.int32), sp)
            prompts[rid], greedy[rid] = prompt, sp.greedy
        res = drain(core, log=log)
        tokens.update(res.tokens)
        errors.update(res.errors)
        finished |= res.finished
    wall = time.perf_counter() - t0
    snap = core.metrics.snapshot()
    counters = {k: int(snap.get(k, {}).get("total", 0)) for k in (
        "engine_requests_failed_total",
        "pressure_swap_fail_downgrades_total", "kv_cow_copies_total")}
    stats = core.stats()
    del core
    gc.collect()                           # free the page pools
    return Served(prompts, greedy, tokens, errors, finished, logits, stats,
                  counters, wall, clock.seconds - c0)


def check_served(s: Served, want_impl: str, log=print) -> List[str]:
    """The engine-side conditions; returns what failed."""
    st, pr = s.stats, s.stats["pressure"]
    hit_tokens = st.get("prefix", {}).get("hit_tokens", 0)
    log(f"paged impl {st['paged_impl']}; requests finished "
        f"{len(s.finished - set(s.errors))}/{len(s.prompts)}; tokens "
        f"generated {sum(map(len, s.tokens.values()))}; preemptions "
        f"{pr['preemptions']} (swaps {pr['swaps']}, swap bytes out "
        f"{pr['swap_bytes_out']} in {pr['swap_bytes_in']}); prefix-hit "
        f"tokens {hit_tokens}; copy-on-write copies "
        f"{s.counters['kv_cow_copies_total']}")
    fails = []
    if st["paged_impl"] != want_impl:
        fails.append(f"paged impl {st['paged_impl']!r}, want {want_impl!r}")
    if s.finished != set(s.prompts) or s.errors:
        fails.append(f"unfinished {sorted(set(s.prompts) - s.finished)}, "
                     f"errors {s.errors}")
    for name in ("engine_requests_failed_total",
                 "pressure_swap_fail_downgrades_total"):
        if s.counters[name]:
            fails.append(f"{name} = {s.counters[name]}")
    if not (pr["swaps"] and pr["swap_bytes_in"] == pr["swap_bytes_out"]):
        fails.append(f"no complete swap round trip: {pr}")
    if not hit_tokens or not s.counters["kv_cow_copies_total"]:
        fails.append("no prefix hit with a copy-on-write copy")
    return fails


def rel_diff(a: np.ndarray, ref: np.ndarray) -> float:
    """max |a - ref| over the reference row's largest |logit|."""
    return float(np.max(np.abs(a - ref)) / np.max(np.abs(ref)))


def check_reference(model, params, s: Served, max_len: int,
                    log=print) -> List[str]:
    """Every logits row a token was picked from -- the prefill's for the
    first token, then one decode step's per token -- against
    ``LM.apply(impl="reference")`` on the prompt and the tokens streamed
    before it.  The reference computes in float32 at the highest matmul
    precision on the same weights: its embedding is cast up, so every
    activation is float32 and each weight is cast where it is used (the
    weights are never held twice).  Sequences are zero-padded to one
    length (one compile; causal attention keeps the padding out of every
    real row)."""
    @jax.jit
    def ref_fn(p, t):
        emb = jax.tree.map(lambda a: a.astype(jnp.float32), p["embedding"])
        with jax.default_matmul_precision("highest"):
            return model.apply({**p, "embedding": emb}, t,
                               impl="reference")[0]

    fails, worst, first_worst, top1, rows, ties = [], 0.0, 0.0, 0, 0, 0
    for rid in sorted(s.prompts):
        prompt, toks = s.prompts[rid], s.tokens.get(rid, [])
        buf = np.zeros((1, max_len), np.int32)
        buf[0, :len(prompt) + len(toks)] = prompt + toks
        ref = np.asarray(ref_fn(params, jnp.asarray(buf)))
        for t, row in sorted(s.logits.get(rid, {}).items()):
            want = ref[len(prompt) - 1 + t]
            d = rel_diff(row, want)
            worst = max(worst, d)
            rows += 1
            ties += int(np.argmax(row) != np.argmax(want))
            if t == 0:
                first_worst = max(first_worst, d)
                top1 += int(np.argmax(row) == np.argmax(want))
            if d > LOGIT_TOL:
                fails.append(f"req {rid} token {t}: logits differ by {d} "
                             f"of the largest logit")
        if len(s.logits.get(rid, {})) != len(toks):
            fails.append(f"req {rid}: {len(toks)} tokens but "
                         f"{len(s.logits.get(rid, {}))} logits rows")
    log(f"logits vs float32 LM.apply(impl='reference'): largest |diff| "
        f"{first_worst} (first tokens), {worst} (all {rows} rows) of the "
        f"row's largest |logit|, tolerance {LOGIT_TOL}; first-token top-1 "
        f"agreement {top1}/{len(s.prompts)}; rows whose top-1 differs "
        f"(near-ties) {ties}")
    return fails


def compare_engines(base: Served, other: Served, log=print) -> List[str]:
    """tp=N against tp=1: the logits rows of every token up to the first
    place the two streams part, within the tolerance of each other
    (past it the contexts differ); the greedy streams compared."""
    fails, worst, rows, same, agree, total = [], 0.0, 0, 0, 0, 0
    for rid in sorted(base.prompts):
        ta, tb = other.tokens[rid], base.tokens[rid]
        n = 0
        while n < min(len(ta), len(tb)) and ta[n] == tb[n]:
            n += 1
        for t in range(min(n + 1, len(tb))):
            d = rel_diff(other.logits[rid][t], base.logits[rid][t])
            worst = max(worst, d)
            rows += 1
            if d > LOGIT_TOL:
                fails.append(f"req {rid} token {t}: logits differ by {d} "
                             f"of the largest logit")
        if base.greedy[rid]:
            same += int(ta == tb)
            agree += n
            total += len(tb)
    log(f"tp engine vs tp=1: largest |diff| {worst} of the row's largest "
        f"|logit| over {rows} rows (tolerance {LOGIT_TOL}); identical "
        f"greedy streams {same}/{sum(base.greedy.values())}; greedy "
        f"tokens before the first divergence {agree}/{total}")
    return fails


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def run_one_chip(model, cfg, seed: int, clock: CompileClock, *,
                 ps: int = PAGE, paged_impl: str = "auto",
                 want_impl: str = "paged") -> List[str]:
    """Serve the waves on one device and check them against the
    reference; returns what failed."""
    waves = make_waves(cfg.vocab_size, ps, seed)
    serve = serve_config(ps, paged_impl=paged_impl)
    params = init_params(model, seed)
    s = serve_waves(model, params, cfg, serve, waves, clock)
    print(f"serving: {s.wall_s:.3f} s wall, of which {s.compile_s:.3f} s "
          f"compiling")
    fails = check_served(s, want_impl)
    c0 = clock.seconds
    fails += check_reference(model, params, s, serve.max_seq_len)
    print(f"reference check: {clock.seconds - c0:.3f} s compiling")
    return fails


def run_tp(model, cfg, seed: int, tp: int, clock: CompileClock, *,
           ps: int = PAGE, paged_impl: str = "auto",
           want_impl: str = "paged") -> List[str]:
    """Serve the waves through the tp=1 engine, then a tp=``tp`` engine
    (one set of weights on the devices at a time); returns what
    failed."""
    if jax.device_count() < tp:
        return [f"--tp {tp} needs {tp} devices, found {jax.device_count()}"]
    waves = make_waves(cfg.vocab_size, ps, seed)
    served, fails = {}, []
    for degree in (1, tp):
        sharding = None
        if degree > 1:      # weights made replicated on the engine's mesh
            plan = plan_tp(cfg, degree, ps)
            sharding = NamedSharding(make_mesh(plan.mesh_shape, plan.axes),
                                     PartitionSpec())
        params = init_params(model, seed, sharding)
        s = serve_waves(model, params, cfg,
                        serve_config(ps, tp=degree, paged_impl=paged_impl),
                        waves, clock)
        del params
        gc.collect()
        print(f"tp={degree} {s.stats.get('tp', '')} serving: "
              f"{s.wall_s:.3f} s wall, of which {s.compile_s:.3f} s "
              f"compiling")
        fails += check_served(s, want_impl)
        served[degree] = s
    return fails + compare_engines(served[1], served[tp])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--tp", type=int, default=1,
                    help="N > 1: compare a tp=N engine with the tp=1 "
                         "engine (needs N chips) instead of the one-chip "
                         "run")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); "
              "this run needs the chip", file=sys.stderr)
        return 2
    clock = CompileClock()
    cfg = get_model_config(ARCH)
    model = build_model(cfg, ParallelConfig())
    print(f"device_kind {devices[0].device_kind}, {len(devices)} "
          f"device(s); {ARCH}: {cfg.num_layers}L d_model {cfg.d_model} "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} head_dim "
          f"{cfg.head_dim} d_ff {cfg.d_ff} vocab {cfg.vocab_size}; "
          f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.tp > 1:
        fails = run_tp(model, cfg, args.seed, args.tp, clock)
    else:
        fails = run_one_chip(model, cfg, args.seed, clock)
    print(f"compile: {clock.seconds:.3f} s over the whole run "
          f"({clock.cache_hits} persistent-cache hits); run wall "
          f"{time.perf_counter() - t0:.3f} s")
    print(f"peak_bytes_in_use {peak_bytes(devices)}")
    for f in fails:
        print(f"FAIL: {f}")
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
