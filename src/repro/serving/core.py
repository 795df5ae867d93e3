"""EngineCore: the persistent, iteration-level serving engine.

vLLM's ``LLMEngine.add_request``/``step`` and FlashInfer's decoupled
plan/run interface expose the same shape: a *core* that owns all serving
state -- page manager, scheduler, pressure manager, radix prefix index,
device page pools, jitted paged functions -- and advances the whole
system exactly one iteration per ``step()`` call.  Frontends, arrival
processes and multi-tenant policies then compose on top, and features
that need a step boundary to hook into (overlapped swap, multi-host
decode) have one.

    core = EngineCore(model=model, params=params, cfg=cfg, serve=serve)
    rid = core.add_request(prompt, SamplingParams(max_new_tokens=32))
    while core.has_work:
        for ev in core.step():          # list[StreamEvent], may be empty
            ...
    core.abort(rid)                     # any time: frees pages, no leaks

Everything persists across requests unconditionally -- the prefix-cache-
only ``_shared_state`` special case of the previous ``ServeEngine`` is
gone: abandoning a stream is now a plain ``abort()`` (free the slot's
pages, cancel its copy-on-write debts, drop any swap stash; shared
prefix pages just lose one reference).

Sampling is per request (``SamplingParams``) with a counter-based RNG:
the key for a request's n-th sampled token is
``fold_in(PRNGKey(params.seed), n)``, so sampled tokens are invariant to
batch composition, co-tenants, preemption and admission order.  The
engine-global ``ServeConfig.temperature/top_k`` knobs survive only as
deprecated defaults for requests submitted without params.

``ServeEngine.generate_stream`` is a thin compatibility wrapper over
this class (submit, drain ``step()``, abort leftovers on close) -- its
greedy output is bit-identical to the pre-core engine.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.config import ModelConfig, ServeConfig
from repro.core.fastattention import default_paged_impl
from repro.serving.faults import (EngineError, InjectedFault, LogitError,
                                  RequestError, RequestRejected,
                                  RequestTimeout, SwapRestoreFailed)
from repro.serving.metrics import (FlightRecorder, LifecycleTracer,
                                   MetricsRegistry, StepPhases)
from repro.serving.paged_cache import OutOfPages, PagedKVCache
from repro.serving.prefix_cache import RadixPrefixIndex
from repro.serving.pressure import PressureManager, copy_pages
from repro.serving.scheduler import (ABORTED, FAILED, FINISHED, PREFILLING,
                                     RUNNING, ContinuousBatchScheduler,
                                     Request, SamplingParams)
from repro.serving.spec import (PromptLookupDrafter, verify_greedy,
                                verify_residual)
from repro.sharding.tp import plan_tp, tp_context


def sample_token(logits, key, *, temperature: float = 1.0, top_k: int = 0):
    if temperature == 0.0 or top_k == 1:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lf = logits.astype(jnp.float32) / max(temperature, 1e-6)
    if top_k > 1:
        # lax.top_k rejects k > vocab; clamping makes oversized k mean
        # "no truncation" instead of a crash
        k = min(top_k, lf.shape[-1])
        vals, _ = jax.lax.top_k(lf, k)
        thresh = vals[..., -1:]
        lf = jnp.where(lf < thresh, -1e30, lf)
    return jax.random.categorical(key, lf).astype(jnp.int32)


class StreamEvent(NamedTuple):
    """One stream event.  ``kind="token"`` (the default, and the only
    kind before the fault-tolerance layer) carries one generated token,
    emitted the step it exists.  ``kind="stop"`` terminates a
    stop-string request whose matched suffix was trimmed (token is -1).
    ``kind="error"`` terminates a FAILED/shed/timed-out request with the
    structured ``detail`` ("code: message") and token -1."""
    request_id: int
    token: int
    index: int            # position within the request's generation
    finished: bool        # True on the request's last event
    kind: str = "token"
    detail: str = ""


class _CountingDeque(deque):
    """Bounded deque that counts evictions instead of losing them
    silently: a full ``append`` still drops the oldest entry (the bound
    is the point), but ``dropped`` records how many orphaned events were
    lost so ``stats()`` can surface the loss."""

    def __init__(self, maxlen: int):
        super().__init__(maxlen=maxlen)
        self.dropped = 0

    def append(self, item) -> None:
        if self.maxlen is not None and len(self) == self.maxlen:
            self.dropped += 1
        super().append(item)


class EngineCore:
    """Persistent iteration-level engine over the paged KV cache.

    One ``step()`` = retire finished sequences, admit waiting/resuming
    requests, spend the prefill token budget on chunked prompt prefill,
    run one fused decode step for every RUNNING slot, and return the
    tokens produced.  All state lives on the core and survives between
    calls -- including the device page pools, so prefix-cache hits keep
    their KV across requests.
    """

    def __init__(self, model, params, cfg: ModelConfig,
                 serve: Optional[ServeConfig] = None, *,
                 fn_cache: Optional[dict] = None, injector=None,
                 detokenize=None, clock=None, metrics=None):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.serve = serve or ServeConfig()
        if self.serve.logit_guard not in ("fail", "ignore"):
            raise ValueError(
                f"unknown logit_guard {self.serve.logit_guard!r}")
        if self.serve.queue_policy not in ("reject", "shed_oldest"):
            raise ValueError(
                f"unknown queue_policy {self.serve.queue_policy!r}")
        if self.serve.spec_mode not in ("off", "lookup"):
            raise ValueError(
                f"unknown spec_mode {self.serve.spec_mode!r}")
        if self.serve.spec_mode != "off" and self.serve.spec_tokens < 1:
            raise ValueError(
                f"spec_tokens must be >= 1 with spec_mode="
                f"{self.serve.spec_mode!r}, got {self.serve.spec_tokens}")
        # fault-injection harness (serving/faults.py): threaded through
        # the page manager and pressure manager; None costs nothing
        self.injector = injector
        # token ids -> text, required only by SamplingParams.stop_strings
        self.detokenize = detokenize
        # engine clock (seconds, monotonic) for deadlines AND all engine
        # timing (step watchdog, spans, phase breakdown); injectable so
        # fake-clock tests observe every timing path deterministically
        self._clock = clock or time.monotonic
        # -- telemetry (serving/metrics.py) ----------------------------
        # The registry is always live: its counters back the ``stats()``
        # view (a handful of integer adds per step).  The step-phase
        # profiler spans (``engine.<phase>``, ``self.phases``) are always
        # opened too, at about a microsecond each with the profiler off.
        # The lifecycle tracer, the phases' engine-clock durations and
        # the flight recorder gate on ``serve.metrics`` -- they are the
        # clock-read overhead.  All of it is host-side between launches:
        # trace-neutral by construction, asserted in
        # tests/test_metrics.py.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        self._c_steps = m.counter("engine_steps_total",
                                  help="engine step() iterations")
        self._c_events = m.counter("engine_events_total",
                                   help="stream events emitted")
        self._c_aborts = m.counter("engine_requests_aborted_total",
                                   help="caller aborts")
        self._c_failed = m.counter("engine_requests_failed_total",
                                   help="requests quarantined "
                                        "(internal/logits/injected)")
        self._c_shed = m.counter("engine_requests_shed_total",
                                 help="requests shed from the bounded "
                                      "waiting queue")
        self._c_timeout = m.counter("engine_requests_timed_out_total",
                                    help="deadline_ms expiries")
        # speculative decoding (serving/spec.py): drafted/accepted token
        # counters plus accept-rate and accepted-run-length histograms;
        # created unconditionally (a handful of registry entries) but
        # only touched when spec_mode != "off"
        self._c_spec_drafted = m.counter(
            "engine_spec_drafted_total",
            help="speculative tokens drafted for verification")
        self._c_spec_accepted = m.counter(
            "engine_spec_accepted_total",
            help="drafted tokens accepted by verification")
        self._h_spec_accept = m.histogram(
            "engine_spec_accept_rate",
            buckets=(0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875,
                     1.0),
            help="per-request accept rate per verify step")
        self._h_spec_run = m.histogram(
            "engine_spec_run_length",
            buckets=tuple(float(i)
                          for i in range(self.serve.spec_tokens + 1)),
            help="accepted draft run length per verify step")
        # each launch's work, as the engine.prefill / engine.decode spans
        # carry it in their args
        self._c_decode_rows = m.counter(
            "engine_decode_rows_total",
            help="sequences in decode launches (one token each)")
        self._c_decode_launches = m.counter(
            "engine_decode_launches_total", help="decode launches")
        self._c_prefill_tokens = m.counter(
            "engine_prefill_tokens_total",
            help="prompt tokens in prefill launches")
        self._c_prefill_launches = m.counter(
            "engine_prefill_launches_total", help="prefill launches")
        self._h_step = m.histogram("engine_step_seconds",
                                   help="step() wall-clock on the "
                                        "engine clock")
        self._g_pages = m.gauge("kv_pages_used",
                                help="physical KV pages in use")
        self._g_pages_hw = m.gauge("kv_pages_peak", high_water=True,
                                   help="peak KV pages in use "
                                        "(current window)")
        self.phases = StepPhases(self._clock)
        self.tracer = (LifecycleTracer(m, self._clock)
                       if self.serve.metrics else None)
        self.flight = (FlightRecorder(self.serve.flight_recorder_steps)
                       if self.serve.metrics else None)
        # most recent flight-recorder dump: taken when an EngineError
        # propagates out of step() or a request is quarantined, so the
        # postmortem survives on the core even if the caller only sees
        # the exception (which also carries it as ``.flight``)
        self.last_flight_dump: Optional[List[dict]] = None
        self._step_rec: Optional[dict] = None
        self._dump_pending = False
        # tensor parallelism (sharding/tp.py): factor serve.tp into
        # kv-head groups x page-row sub-shards and bind a 2-D mesh; the
        # paged forward fns trace under tp_context, flipping the
        # attention/MLP layers onto their shard_map TP bodies
        self.tp_plan = None
        self.tp_mesh = None
        if self.serve.tp > 1:
            from repro.launch.mesh import make_mesh
            plan = plan_tp(cfg, self.serve.tp, self.serve.page_size,
                           collectives=self.serve.tp_collectives,
                           ar_chunks=self.serve.tp_ar_chunks,
                           first_chunk_frac=self.serve.tp_first_chunk_frac)
            if jax.device_count() < plan.tp:
                raise ValueError(
                    f"tp={plan.tp} needs {plan.tp} devices, "
                    f"found {jax.device_count()}")
            self.tp_plan = plan
            self.tp_mesh = make_mesh(plan.mesh_shape, plan.axes)
            # weights replicated on the mesh once (a no-op when they were
            # made there): params left on one device would be re-sent to
            # every shard on every launch
            self.params = jax.device_put(
                params, NamedSharding(self.tp_mesh, PartitionSpec()))
        # jitted paged prefill/decode triples keyed by (resolved impl,
        # tp plan); shared with the ServeEngine wrapper so clearing one
        # clears both
        self._paged_fn_cache = fn_cache if fn_cache is not None else {}
        # how many times the chunked-prefill function was *traced* (not
        # called): the trace-count test asserts it stays bounded by
        # launch widths no matter how many prompt lengths stream through
        self.prefill_trace_count = 0
        # prefill chunk *launches* (calls, not traces): prefix-cache hits
        # skip the matched prefix's launches entirely, asserted in tests
        self.prefill_launches = 0
        # speculative verify launches/traces, counted apart from prefill
        # so the prefill trace/launch assertions hold with spec on, and
        # spec_mode="off" provably never touches the verify fn
        self.spec_launches = 0
        self.spec_trace_count = 0
        self._warned_legacy_sampling = False
        self._next_id = 0
        self.reset()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Drop every request, page, stash and cached prefix and rebuild
        the serving state from ``self.serve``.  Jit caches, trace
        counters and the metrics registry survive (they are keyed by the
        engine's lifetime, not its state) -- use
        ``reset_metrics_window()`` to open a fresh measurement window."""
        serve = self.serve
        self.mgr = PagedKVCache(serve.pool_pages(), serve.page_size,
                                serve.max_batch, serve.max_pages_per_seq,
                                injector=self.injector,
                                metrics=self.metrics)
        self.prefix = (RadixPrefixIndex(self.mgr, serve.page_size,
                                        serve.prefix_cache_pages,
                                        metrics=self.metrics)
                       if serve.prefix_cache else None)
        self.sched = ContinuousBatchScheduler(
            self.mgr, serve.max_batch, admission=serve.admission,
            watermark_pages=serve.watermark, prefix_cache=self.prefix,
            tracer=self.tracer)
        self.pressure = PressureManager(self.cfg, serve, self.mgr,
                                        self.sched,
                                        prefix_cache=self.prefix,
                                        injector=self.injector,
                                        metrics=self.metrics,
                                        tracer=self.tracer)
        if self.tracer is not None:
            self.tracer.reset()        # every request is gone with the state
        # speculation drafter (serving/spec.py): per-request n-gram
        # indexes and accept-rate EMAs die with the requests on reset.
        # ``spec is None`` IS the off switch -- the decode phase branches
        # on it once per step and the off path stays byte-for-byte the
        # plain decode step.
        self.spec = (PromptLookupDrafter(
            max_tokens=serve.spec_tokens,
            ngram_max=serve.spec_ngram_max,
            ngram_min=serve.spec_ngram_min,
            ema_alpha=serve.spec_ema_alpha)
            if serve.spec_mode == "lookup" else None)
        self.pools = None              # device pools, materialised lazily
        self.next_tok = np.zeros((serve.max_batch,), np.int32)
        self.requests: Dict[int, Request] = {}     # live (unfinished) only
        # events a generate_stream drain stepped out for requests no
        # drain owns (direct add_request users): step() hands each event
        # to exactly one caller, so mixed-mode users recover them here
        # (drops past the bound are counted, see stats()["orphans_dropped"])
        self.orphan_events: _CountingDeque = _CountingDeque(maxlen=4096)
        # -- fault-tolerance state -------------------------------------
        # terminal error events produced outside a step() (queue
        # shedding at submit time): the next step() returns them first
        self._pending_events: List[StreamEvent] = []
        # per-request incremental detokenisation state for stop_strings:
        # id -> {"text": decoded generation, "ends": char offset at the
        # end of each generated token}
        self._stop_state: Dict[int, dict] = {}
        self.last_error: Optional[str] = None

    # ------------------------------------------------------------------
    # registry-backed counters
    # ------------------------------------------------------------------
    # stats() is a *view* over the metrics registry: each attribute the
    # pre-telemetry engine kept as a plain int is now a read-only
    # property over the registry's current window.  Cumulative Prometheus
    # totals survive reset(); reset_metrics_window() is what opens a
    # fresh measurement window (bench warmups call it).
    @property
    def steps(self) -> int:
        return self._c_steps.window

    @property
    def events_emitted(self) -> int:
        return self._c_events.window

    @property
    def aborts(self) -> int:
        return self._c_aborts.window

    @property
    def failed_count(self) -> int:
        return self._c_failed.window

    @property
    def shed_count(self) -> int:
        return self._c_shed.window

    @property
    def timed_out_count(self) -> int:
        return self._c_timeout.window

    @property
    def step_s_high_water(self) -> float:
        return self._h_step.window_max

    def reset_metrics_window(self) -> None:
        """Open a fresh measurement window: zero every windowed counter,
        histogram and high-water gauge in the registry (cumulative
        Prometheus ``_total`` values are untouched), clear the tracer's
        completed-request log and the flight recorder's ring.  Bench
        warmups call this so the timed region starts from zero."""
        self.metrics.reset_window()
        if self.tracer is not None:
            self.tracer.clear_completed()
        if self.flight is not None:
            self.flight.records.clear()
        self.mgr.reset_peak()

    def export_prometheus(self) -> str:
        """Prometheus text-format (0.0.4) exposition of the registry."""
        return self.metrics.to_prometheus()

    def chrome_trace(self, records: Optional[List[dict]] = None) -> dict:
        """Chrome ``trace_event`` JSON for the flight recorder's current
        ring (or a prior ``dump()``): load the result into
        chrome://tracing or Perfetto for a step/phase timeline."""
        if self.flight is None:
            return {"traceEvents": [], "displayTimeUnit": "ms"}
        return self.flight.to_chrome_trace(records)

    @property
    def has_work(self) -> bool:
        return self.sched.has_work

    def stats(self) -> dict:
        """Point-in-time engine statistics (live objects, not a log)."""
        mgr, sched = self.mgr, self.sched
        out = {
            "steps": self.steps,
            "events_emitted": self.events_emitted,
            "aborts": self.aborts,
            "waiting": len(sched.waiting),
            "resuming": len(sched.resuming),
            "active_slots": sum(1 for r in sched.slots if r is not None),
            "finished": sched.finished_count,
            "pages_used": mgr.used_pages,
            "pages_free": mgr.free_pages,
            "pages_peak": mgr.peak_used_pages,
            "peak_utilization": mgr.peak_utilization,
            "prefill_launches": self.prefill_launches,
            "prefill_trace_count": self.prefill_trace_count,
            "paged_impl": self._paged_impl(),
            "orphan_events_pending": len(self.orphan_events),
            "orphans_dropped": self.orphan_events.dropped,
            "pressure": dict(self.pressure.stats),
            "host_pool_pages": self.pressure.host_pool.used_pages,
            "health": {
                "failed": self.failed_count,
                "shed": self.shed_count,
                "timed_out": self.timed_out_count,
                "swap_retries": self.pressure.stats["swap_retries"],
                "swap_fail_downgrades":
                    self.pressure.stats["swap_fail_downgrades"],
                "last_error": self.last_error,
                "step_s_high_water": self.step_s_high_water,
            },
        }
        if self.spec is not None:
            drafted = self._c_spec_drafted.window
            accepted = self._c_spec_accepted.window
            out["spec"] = {
                "drafted": drafted,
                "accepted": accepted,
                "accept_rate": (accepted / drafted) if drafted else 0.0,
                "verify_launches": self.spec_launches,
                "verify_trace_count": self.spec_trace_count,
            }
        if self.injector is not None:
            out["injected_faults"] = self.injector.stats()
        if self.prefix is not None:
            out["prefix"] = dict(self.prefix.stats)
            out["prefix_cached_pages"] = self.prefix.cached_pages
        if self.tp_plan is not None:
            out["tp"] = {"tp": self.tp_plan.tp, "g": self.tp_plan.g,
                         "s": self.tp_plan.s,
                         "collectives": self.tp_plan.collectives}
        return out

    # ------------------------------------------------------------------
    # request intake
    # ------------------------------------------------------------------
    def _resolve_sampling(self, req: Request, seed_offset: int = 0) -> None:
        """Give a params-less request its SamplingParams from the
        deprecated engine-global knobs (warning once per core when they
        were actually changed from their defaults).  The legacy seed
        folds in the request id so co-scheduled legacy requests do not
        sample identical streams."""
        if req.sampling is not None:
            return
        serve = self.serve
        if serve.sampling_overridden and not self._warned_legacy_sampling:
            self._warned_legacy_sampling = True
            warnings.warn(
                "engine-global ServeConfig.temperature/top_k are "
                "deprecated: pass SamplingParams per request "
                "(Request(sampling=...) or EngineCore.add_request)",
                DeprecationWarning, stacklevel=4)
        req.sampling = SamplingParams(
            temperature=serve.temperature, top_k=serve.top_k,
            seed=serve.seed + seed_offset + int(req.id),
            max_new_tokens=req.max_new_tokens,
            stop_token_ids=(req.eos_id,) if req.eos_id is not None else ())

    def submit_request(self, req: Request, *, seed_offset: int = 0
                       ) -> Request:
        """Validate and enqueue a pre-built ``Request`` (the
        generate_stream compatibility path).  Raises ``RequestRejected``
        (a ValueError) when the request can never fit the pool, needs a
        missing detokenizer for its stop_strings, or the bounded waiting
        queue is full under ``queue_policy="reject"``; plain ValueError
        when its id collides with a live request.  Under
        ``queue_policy="shed_oldest"`` a full queue sheds its oldest
        waiting request instead (structured error event on the next
        step)."""
        live = self.requests.get(req.id)
        if live is not None and live.state not in (FINISHED, ABORTED,
                                                   FAILED):
            raise ValueError(f"request id {req.id} is already live")
        self._resolve_sampling(req, seed_offset)
        if req.sampling.stop_strings and self.detokenize is None:
            raise RequestRejected(
                f"request {req.id}: stop_strings need a detokenize= "
                "callable on the engine", request_id=req.id)
        mw = self.serve.max_waiting
        if mw and len(self.sched.waiting) >= mw:
            if self.serve.queue_policy == "reject":
                raise RequestRejected(
                    f"request {req.id}: waiting queue full "
                    f"({mw} requests)", request_id=req.id)
            victim = self.sched.waiting[0]   # shed_oldest
            self._quarantine(victim, RequestRejected(
                f"request {victim.id}: shed from full waiting queue "
                f"({mw} requests) by newer arrival",
                request_id=victim.id))
        req.submit_t = self._clock()
        self.sched.submit(req)          # validates against the pool
        self.requests[req.id] = req
        if self.tracer is not None:
            self.tracer.on_submit(req)
        return req

    def add_request(self, prompt, sampling: Optional[SamplingParams] = None,
                    *, request_id: Optional[int] = None,
                    max_new_tokens: Optional[int] = None,
                    eos_id: Optional[int] = None) -> int:
        """Submit a new generation request; returns its id.  ``prompt``
        is a 1-D sequence of token ids.  Without ``sampling`` the
        default greedy ``SamplingParams()`` applies (the aliases fold
        into it) -- the new API never inherits the deprecated
        engine-global knobs; only Requests submitted through
        ``generate_stream`` without params do.  The request queues FIFO
        and is admitted by a later ``step()``."""
        if sampling is None:
            sampling = SamplingParams()
        rid = request_id
        if rid is None:
            while self._next_id in self.requests:
                self._next_id += 1
            rid = self._next_id
            self._next_id += 1
        req = Request(id=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_id=eos_id, sampling=sampling)
        self.submit_request(req)
        return rid

    def get_request(self, request_id: int) -> Optional[Request]:
        return self.requests.get(request_id)

    def abort(self, request_id: int) -> bool:
        """Cancel a request anywhere in its lifecycle: waiting, resuming
        (its host swap stash is dropped), mid-prefill or mid-decode (its
        slot's pages are freed -- shared prefix pages just decref -- and
        its pending COW debts die with it).  Returns False for an
        unknown or already-finished id.  Idempotent."""
        req = self.sched.abort(request_id)
        if req is None:
            return False
        if self.pressure.holds(request_id):
            self.pressure.drop(request_id, reason="abort")
        self.requests.pop(request_id, None)
        self._stop_state.pop(request_id, None)
        if self.spec is not None:
            self.spec.forget(request_id)
        self._c_aborts.inc()
        if self.tracer is not None:
            self.tracer.on_abort(req)
        return True

    # ------------------------------------------------------------------
    # fault isolation
    # ------------------------------------------------------------------
    def _quarantine(self, req: Request, exc: Exception,
                    events: Optional[List[StreamEvent]] = None) -> None:
        """Fail exactly one request in place: its slot's pages are freed
        (shared prefix pages decref'd), its pending COW debts cancelled,
        any host swap stash dropped, and a terminal ``kind="error"``
        event emitted -- co-tenant requests keep serving and their
        outputs are unchanged (greedy sampling is batch-composition
        invariant).  ``events=None`` queues the event for the next
        ``step()`` (submit-time shedding has no step underway)."""
        if isinstance(exc, RequestError):
            detail = exc.detail
        elif isinstance(exc, InjectedFault):
            detail = f"injected: {exc}"
        else:
            detail = f"internal: {exc}"
        self.sched.abort(req.id)        # frees slot/pages/COW wherever it is
        if self.pressure.holds(req.id):
            self.pressure.drop(req.id, reason="fail")
        req.state = FAILED
        req.error = detail
        req.slot = None
        self.requests.pop(req.id, None)
        self._stop_state.pop(req.id, None)
        if self.spec is not None:
            self.spec.forget(req.id)
        if isinstance(exc, RequestTimeout):
            self._c_timeout.inc()
            code = "timed_out"
        elif isinstance(exc, RequestRejected):
            self._c_shed.inc()
            code = "shed"
        else:
            self._c_failed.inc()
            code = "failed"
        self.last_error = f"request {req.id}: {detail}"
        if self.tracer is not None:
            self.tracer.on_fail(req, code)
        if self._step_rec is not None:
            self._step_rec["quarantined"].append(
                {"request_id": req.id, "code": code, "detail": detail})
            self._dump_pending = True
        elif self.flight is not None:
            # submit-time shedding happens outside any step: dump the
            # ring as it stands so the postmortem is not lost
            self.last_flight_dump = self.flight.dump()
        ev = StreamEvent(req.id, -1, len(req.generated), True,
                         kind="error", detail=detail)
        (events if events is not None else self._pending_events).append(ev)

    # ------------------------------------------------------------------
    # jitted paged functions
    # ------------------------------------------------------------------
    def _paged_impl(self) -> str:
        if self.serve.paged_impl == "auto":
            return default_paged_impl()
        return self.serve.paged_impl

    def _paged_fns(self):
        """Jitted paged fns keyed on the resolved impl so a serve-config
        change after first use is honoured: (scan prefill, chunked
        prefill, fused decode step, speculative verify).  The scan
        prefill retraces once per distinct prompt length (that is why it
        is the legacy path); the chunked prefill traces once per launch
        width -- chunk shape, page-table width and position offsets are
        all runtime values.  The verify fn is the chunked prefill
        forward returning the FULL (B, C, V) logits (acceptance needs
        every position, not just the last valid row); it is only ever
        traced when a verify step actually launches, so spec_mode="off"
        never pays for it."""
        impl = self._paged_impl()
        if impl == "paged" and self.serve.page_size % 128:
            raise ValueError(
                f"page_size={self.serve.page_size} must be a multiple of "
                "128 (TPU lane width) for the compiled Pallas paged "
                "kernel; pick a 128-multiple or paged_impl="
                "'paged_reference'")
        key = (impl, self.tp_plan)
        if key not in self._paged_fn_cache:
            model = self.model
            core = self

            def dec(params, tok, pools, table, pos):
                return model.decode_step_paged(params, tok, pools, table,
                                               pos, impl=impl)

            def pre_scan(params, prompt, pools, table_row, pos0):
                # pos0: (1,) int32 runtime offset -- a prefix-cache hit
                # scans only the uncached prompt tail from matched_len
                s = prompt.shape[1]

                def step(c, t):
                    lg, c = model.decode_step_paged(
                        params, prompt[:, t], c, table_row,
                        pos0 + t.astype(jnp.int32), impl=impl)
                    return c, lg

                pools, lgs = jax.lax.scan(step, pools, jnp.arange(s))
                return pools, lgs[-1]

            def pre_chunk(params, chunk, pools, table_row, pos_start,
                          n_valid):
                core.prefill_trace_count += 1  # repro-lint: disable=trace-impurity (trace-count marker)
                logits, pools = model.prefill_chunk_paged(
                    params, chunk, pools, table_row, pos_start, n_valid,
                    impl=impl)
                # the chunk's last *valid* row: only meaningful logits --
                # padding rows attended through the scratch page
                last = jnp.take_along_axis(
                    logits, jnp.maximum(n_valid - 1, 0)[:, None, None],
                    axis=1)[:, 0]
                return pools, last

            def verify(params, chunk, pools, table, pos_start, n_valid):
                core.spec_trace_count += 1  # repro-lint: disable=trace-impurity (trace-count marker)
                logits, pools = model.prefill_chunk_paged(
                    params, chunk, pools, table, pos_start, n_valid,
                    impl=impl)
                return pools, logits

            self._paged_fn_cache[key] = tuple(
                self._tp_wrap(jax.jit(f, donate_argnums=(2,)))
                for f in (pre_scan, pre_chunk, dec, verify))
        return self._paged_fn_cache[key]

    def _tp_wrap(self, fn):
        """Enter the tensor-parallel context around a jitted paged fn so
        the layer code traces onto its shard_map TP bodies (jit traces at
        call time; the contextvar must be live then, not at jit time)."""
        if self.tp_mesh is None:
            return fn
        mesh, plan = self.tp_mesh, self.tp_plan

        def wrapped(*args):
            with tp_context(mesh, plan):
                return fn(*args)

        return wrapped

    # ------------------------------------------------------------------
    # sampling (per-request counter-based RNG)
    # ------------------------------------------------------------------
    def _sample(self, req: Request, logits_row) -> int:
        """Sample the request's next token from its own RNG stream:
        key = fold_in(PRNGKey(seed), token_index).  Greedy requests take
        the argmax (no key consumed), so greedy output is bit-identical
        whatever else shares the batch."""
        sp = req.sampling
        if sp.greedy:
            return int(np.asarray(
                jnp.argmax(logits_row, axis=-1)).ravel()[0])
        key = jax.random.fold_in(jax.random.PRNGKey(sp.seed),
                                 len(req.generated))
        tok = sample_token(jnp.atleast_2d(logits_row), key,
                           temperature=sp.temperature, top_k=sp.top_k)
        return int(np.asarray(tok).ravel()[0])

    def _fire(self, site: str) -> None:
        if self.injector is not None:
            self.injector.fire(site)

    def _guard_logits(self, req: Request, row) -> None:
        """NaN/Inf guard on one request's logits row: under
        ``logit_guard="fail"`` a non-finite row fails only the offending
        request (LogitError -> quarantine); "ignore" samples through it
        (argmax of all-NaN picks index 0 -- garbage, but contained)."""
        if self.serve.logit_guard != "fail":
            return
        if not bool(np.asarray(jnp.all(jnp.isfinite(row)))):
            raise LogitError(
                f"request {req.id}: non-finite logits at token "
                f"{len(req.generated)}", request_id=req.id)

    def _first_token(self, req: Request, slot: int, last_logits,
                     events: List[StreamEvent]) -> None:
        """Sample a freshly-prefilled sequence's first token and flip
        the request into the decoding state.  Sampling faults (injected,
        non-finite logits) quarantine this request only."""
        phase = self.phases
        try:
            self._fire("sample")
            with phase("guard"):
                self._guard_logits(req, last_logits)
            with phase("sample"):
                tok = self._sample(req, last_logits)
        except (InjectedFault, RequestError) as e:
            self._quarantine(req, e, events)
            return
        with phase("stream"):
            req.state = RUNNING
            req.generated.append(tok)
            self.next_tok[slot] = tok
            if self.tracer is not None:
                # first-token opens the running span; on_token counts it
                # so TPOT sees the same token stream the bench does
                self.tracer.on_first_token(req)
                self.tracer.on_token(req)
            self._stream(req, events)

    # ------------------------------------------------------------------
    # event emission (stop-string holdback)
    # ------------------------------------------------------------------
    def _stream(self, req: Request, events: List[StreamEvent]) -> None:
        """Emit the request's not-yet-streamed generated tokens.

        Without stop_strings every new token streams immediately (the
        pre-existing behaviour, bit for bit).  With stop_strings the
        generation is detokenised incrementally; a match ends the
        request with the matched suffix trimmed from the stream, and
        while no match exists the longest text suffix that is a prefix
        of some stop string is *held back* -- a stop string spanning a
        token boundary must never be half-emitted.  Held tokens flush
        when the request finishes for another reason (stop token id,
        max_new_tokens)."""
        gen = req.generated
        sp = req.sampling
        if not sp.stop_strings:
            while req.emitted < len(gen):
                i = req.emitted
                fin = req.done and i == len(gen) - 1
                events.append(StreamEvent(req.id, gen[i], i, fin))
                req.emitted += 1
            return
        st = self._stop_state.setdefault(req.id, {"text": "", "ends": []})
        for i in range(len(st["ends"]), len(gen)):
            # cumulative-prefix decode: piece i is whatever text the
            # i-th token added (robust to multi-token glyphs)
            st["text"] = self.detokenize(gen[:i + 1])
            st["ends"].append(len(st["text"]))
        text, ends = st["text"], st["ends"]
        match = -1
        for s in sp.stop_strings:
            p = text.find(s)
            if p != -1 and (match == -1 or p < match):
                match = p
        if match != -1:
            # emit tokens wholly before the match; the token containing
            # the match start is trimmed with the rest of the suffix
            safe = sum(1 for e in ends if e <= match)
            while req.emitted < safe:
                i = req.emitted
                events.append(StreamEvent(req.id, gen[i], i, False))
                req.emitted += 1
            req.stop_matched = True     # terminal: done is now True
            matched = max((s for s in sp.stop_strings
                           if text.startswith(s, match)), key=len)
            events.append(StreamEvent(req.id, -1, req.emitted, True,
                                      kind="stop", detail=matched))
            self._stop_state.pop(req.id, None)
            return
        if req.done:                    # stop token / length: flush all
            while req.emitted < len(gen):
                i = req.emitted
                events.append(StreamEvent(req.id, gen[i], i,
                                          i == len(gen) - 1))
                req.emitted += 1
            self._stop_state.pop(req.id, None)
            return
        hold = 0
        for s in sp.stop_strings:
            for k in range(min(len(s) - 1, len(text)), 0, -1):
                if text.endswith(s[:k]):
                    hold = max(hold, k)
                    break
        safe_chars = len(text) - hold
        safe = sum(1 for e in ends if e <= safe_chars)
        while req.emitted < safe:
            i = req.emitted
            events.append(StreamEvent(req.id, gen[i], i, False))
            req.emitted += 1

    # ------------------------------------------------------------------
    # page plumbing
    # ------------------------------------------------------------------
    def _ensure_pools(self) -> None:
        if self.pools is None:
            self.pools = self.model.init_paged_cache(self.mgr.num_pages,
                                                     self.mgr.page_size)
            if self.tp_mesh is not None:
                # shard the pools over the TP mesh (kv heads over the
                # head-group axis, within-page rows over the page-row
                # axis) so each device holds 1/tp of the KV budget
                sh = self.model.paged_cache_sharding(
                    self.tp_mesh, self.mgr.num_pages, self.mgr.page_size)
                self.pools = jax.device_put(self.pools, sh)

    def _apply_cow(self) -> None:
        """Replay pending copy-on-write page moves on the device pools:
        the host manager already rewired the page table, the contents
        must follow before the next launch reads or writes the copy."""
        mgr = self.mgr
        if not mgr.cow_pending:
            return
        pairs, mgr.cow_pending = mgr.cow_pending, []
        try:
            self._fire("cow_copy")
        except InjectedFault:
            # debt restored untouched: the caller quarantines the grower
            # (whose abort cancels exactly the debts that die with it)
            # and every other pair stays owed for the next _apply_cow
            mgr.cow_pending = pairs
            raise
        with self.phases("cow_replay"):
            self.pools = copy_pages(self.pools, [s for s, _ in pairs],
                                    [d for _, d in pairs])

    def _grow(self, slot: int, n: int) -> None:
        """``mgr.append(slot, n)`` with page-pressure relief: on
        OutOfPages, reclaim prefix-cache leaves or evict the newest-
        admitted other sequence (swap or recompute) and retry.
        Terminates because submit-time validation guarantees any single
        request fits the pool alone.  Applies any resulting
        copy-on-write page copies to the device pools."""
        while True:
            try:
                self.mgr.append(slot, n)
                self._apply_cow()
                return
            except OutOfPages:
                self.pressure.relieve(self.pools, protect=slot)

    @staticmethod
    def _prefill_groups(jobs, width: int):
        """Pack this step's prefill jobs into batched launches: first-fit
        into the earliest group that has room and no job for the same
        slot yet (a slot's chunk k+1 must launch after its chunk k; the
        first-fit order preserves that).  Distinct sequences' chunks ride
        one ``prefill_chunk_paged`` call instead of one launch each."""
        groups: list = []
        for job in jobs:
            slot = job[0]
            for g in groups:
                if len(g) < width and all(j[0] != slot for j in g):
                    g.append(job)
                    break
            else:
                groups.append([job])
        return groups

    def _resume_decode(self, req: Request, slot: int) -> None:
        """Flip a resumed sequence whose prefill state is fully restored
        back into decode: its next input token was already sampled before
        the preemption, so nothing is emitted here."""
        req.state = RUNNING
        self.next_tok[slot] = req.generated[-1]

    def _check_invariants(self) -> None:
        self.mgr.check_invariants(
            extern_refs=self.prefix.page_refs() if self.prefix else None)

    # ------------------------------------------------------------------
    # the step
    # ------------------------------------------------------------------
    def step(self) -> List[StreamEvent]:
        """Advance the engine one iteration and return the events it
        produced (possibly none: a step may be all prefill, or idle).
        Event order within a step: terminal events queued since the last
        step (shed requests), deadline expiries, then first tokens of
        sequences whose prefill completed, then one decode token per
        running slot.  Per-request failures (injected faults, non-finite
        logits) quarantine the offending request mid-step -- survivors'
        tokens are bit-identical to a fault-free run; only an
        ``EngineError`` (unrecoverable engine-level breach) propagates
        out -- carrying the flight-recorder dump as ``.flight``."""
        t0 = self._clock()
        if self.flight is not None:
            self._step_rec = {
                "step": self._c_steps.value, "t_start": t0,
                "phases": {}, "events": 0, "quarantined": [],
                "faults_fired": (self.injector.total_fired
                                 if self.injector is not None else 0),
            }
        err: Optional[EngineError] = None
        try:
            with self.phases.step(None if self._step_rec is None
                                  else self._step_rec["phases"]):
                events = self._step()
            if self._step_rec is not None:
                self._step_rec["events"] = len(events)
            return events
        except EngineError as e:
            err = e
            raise
        finally:
            dt = self._clock() - t0
            self._h_step.observe(dt)
            self._g_pages.set(self.mgr.used_pages)
            self._g_pages_hw.set(self.mgr.used_pages)
            rec, self._step_rec = self._step_rec, None
            if rec is not None:
                rec["dur_s"] = dt
                rec["pages_used"] = self.mgr.used_pages
                rec["faults_fired"] = \
                    (self.injector.total_fired
                     if self.injector is not None else 0) \
                    - rec["faults_fired"]
                if err is not None:
                    rec["error"] = str(err)
                self.flight.record(rec)
                for phase, pdt in rec["phases"].items():
                    self.metrics.observe(
                        f"engine_phase_{phase}_seconds", pdt)
                if err is not None or self._dump_pending:
                    self._dump_pending = False
                    self.last_flight_dump = self.flight.dump()
                    if err is not None:
                        err.flight = self.last_flight_dump

    def _step(self) -> List[StreamEvent]:
        events: List[StreamEvent] = self._pending_events
        self._pending_events = []
        sched, mgr, serve = self.sched, self.mgr, self.serve
        if not sched.has_work:
            return events
        self._c_steps.inc()
        rec = self._step_rec
        phase = self.phases
        ps = mgr.page_size
        self._ensure_pools()
        pre_scan, pre_chunk, decode, verify = self._paged_fns()

        with phase("schedule"):
            # deadline sweep before admission, so an already-expired
            # waiting request never takes a slot; expired running
            # requests are quarantined cleanly (pages freed, stash
            # dropped) with a structured timeout event
            now = self._clock()
            expired = [r for r in list(sched.waiting)
                       + list(sched.resuming) if r.deadline_expired(now)]
            expired += [r for _, r in sched.running()
                        if r.deadline_expired(now) and not r.done]
            for req in expired:
                self._quarantine(req, RequestTimeout(
                    f"request {req.id}: deadline "
                    f"{req.sampling.deadline_ms:g}ms exceeded",
                    request_id=req.id), events)
            for req in sched.retire():
                self.requests.pop(req.id, None)
                if self.spec is not None:
                    self.spec.forget(req.id)
            admitted = sched.admit()
        # RESUMING path: swap-preempted requests re-admitted by the
        # scheduler get their stashed KV copied back into the pages
        # admission just materialised (their shared prefix was re-shared
        # from the index); a sequence that was decoding when evicted
        # rejoins the decode batch directly (its next input token was
        # sampled before the preemption).  A stash whose resume was
        # downgraded to recompute is dropped.
        with phase("swap"):
            for slot, req in admitted:
                if self.pressure.holds(req.id):
                    if req.resume_kind == "swap":
                        try:
                            self.pools = self.pressure.restore(
                                self.pools, slot, req)
                        except SwapRestoreFailed:
                            # H2D failed past its retry budget: downgrade
                            # the resume to recompute -- unwind the slot,
                            # drop the stash, requeue.  Strictly slower,
                            # never a failed request.
                            self.pressure.drop(req.id)
                            self.pressure._bump("swap_fail_downgrades")
                            req.resume_kind = "recompute"
                            req.resume_shared_len = 0
                            sched.preempt(slot)
                            continue
                    else:
                        self.pressure.drop(req.id)
                if req.state == RUNNING:
                    self.next_tok[slot] = req.generated[-1]
        if rec is not None:
            rec["waiting"] = len(sched.waiting)
            rec["resuming"] = len(sched.resuming)
            rec["prefilling"] = len(sched.prefilling())
            rec["decoding"] = len(sched.decoding())
        if not admitted and not sched.running():
            if not sched.waiting and not sched.resuming:
                return events           # everything retired
            if self.injector is not None:
                # an injected admission fault can unwind this step's
                # whole admission -- benign, the queue retries next step
                return events
            # submit-time validation guarantees the head of either queue
            # fits an empty pool (the watermark is waived when no slot is
            # occupied); kept as a tripwire -- reaching it means engine
            # state is inconsistent, not that one request is bad
            req = (sched.resuming or sched.waiting)[0]
            raise EngineError(
                f"pool too small for request {req.id}: needs "
                f"{-(-req.target_len // ps)} pages, pool has "
                f"{mgr.num_pages - 1}")
        if serve.debug_invariants:
            self._check_invariants()

        # ---- prefill phase -------------------------------------------
        chunk = serve.prefill_chunk_tokens
        budget = serve.prefill_budget_tokens
        if serve.prefill_mode == "scan":
            # legacy: the whole uncached (re)prefill tail at once, one
            # token per scan step, retraced per length (equivalence
            # oracle); a prefix-cache hit starts the scan at matched_len
            # over the shared pages
            for slot, req in admitted:
                if sched.slots[slot] is not req \
                        or req.state != PREFILLING:
                    continue            # preempted again, or swap-resumed
                try:
                    # launch-site faults fire BEFORE any page mutation:
                    # the untouched prefill simply retries next step
                    self._fire("prefill_launch")
                except InjectedFault:
                    continue
                start = req.prefilled
                toks = req.prefill_tokens[start:]
                try:
                    with phase("grow"):
                        self._grow(slot, len(toks))
                except InjectedFault as e:
                    self._quarantine(req, e, events)
                    continue
                with phase("prefill") as span:
                    n = len(toks)
                    span.args(rows=1, tokens=n,
                              attended=n * start + n * (n + 1) // 2)
                    self._c_prefill_launches.inc()
                    self._c_prefill_tokens.inc(n)
                    # repro-lint: disable=retrace-hazard (the scan
                    # oracle deliberately traces per prompt length; the
                    # production path is the chunked paged prefill)
                    self.pools, last_logits = pre_scan(
                        self.params, jnp.asarray(toks[None]), self.pools,
                        jnp.asarray(mgr.device_row(slot)),
                        jnp.full((1,), start, jnp.int32))
                req.prefilled = start + len(toks)
                if req.generated:
                    self._resume_decode(req, slot)
                else:
                    self._first_token(req, slot, last_logits, events)
        else:
            # chunked: fixed-size chunks through the full forward, jobs
            # for distinct sequences batched into one launch, padded to
            # the next power-of-two row count (a lone prefilling prompt
            # stays a 1-row launch; traces stay bounded by
            # log2(max_batch)+1 widths, never by prompt length)
            width = serve.max_batch
            for group in self._prefill_groups(
                    sched.prefill_schedule(budget, chunk), width):
                try:
                    # fires BEFORE the group's page growth; skipping the
                    # REST of this step's prefill keeps chunk order (a
                    # slot's chunk k+1 must never launch before chunk k)
                    self._fire("prefill_launch")
                except InjectedFault:
                    break
                live = []
                with phase("grow"):
                    for slot, req, start, n in group:
                        if sched.slots[slot] is not req \
                                or req.state != PREFILLING:
                            continue    # victim of an earlier _grow
                        try:
                            self._grow(slot, n)
                        except InjectedFault as e:
                            self._quarantine(req, e, events)
                            continue
                        live.append((slot, req, start, n))
                # _grow may have evicted an earlier group member
                live = [(s, r, st, n) for s, r, st, n in live
                        if sched.slots[s] is r]
                if not live:
                    continue
                with phase("prefill") as span:
                    bw = 1
                    while bw < len(live):
                        bw *= 2
                    bw = min(bw, width)
                    buf = np.zeros((bw, chunk), np.int32)
                    table = np.full((bw, mgr.max_pages_per_seq),
                                    mgr.SCRATCH, np.int32)
                    pos0 = np.zeros((bw,), np.int32)
                    nval = np.zeros((bw,), np.int32)
                    tokens = attended = 0
                    for i, (slot, req, start, n) in enumerate(live):
                        buf[i, :n] = req.prefill_tokens[start:start + n]
                        table[i] = mgr.table[slot]
                        pos0[i] = start
                        nval[i] = n
                        tokens += n
                        attended += n * start + n * (n + 1) // 2
                    span.args(rows=len(live), tokens=tokens,
                              attended=attended)
                    self.prefill_launches += 1
                    self._c_prefill_launches.inc()
                    self._c_prefill_tokens.inc(tokens)
                    self.pools, last_logits = pre_chunk(
                        self.params, jnp.asarray(buf), self.pools,
                        jnp.asarray(table), jnp.asarray(pos0),
                        jnp.asarray(nval))
                for i, (slot, req, start, n) in enumerate(live):
                    req.prefilled = start + n
                    if not req.prefill_done:
                        continue
                    if req.generated:   # recompute-resume finished
                        self._resume_decode(req, slot)
                    else:
                        self._first_token(req, slot,
                                          last_logits[i:i + 1], events)

        # ---- decode phase --------------------------------------------
        cand = [(s, r) for s, r in sched.decoding() if not r.done]
        try:
            # fires BEFORE the decode _grows: the whole decode phase is
            # skipped untouched this step and retries on the next one
            self._fire("decode_launch")
        except InjectedFault:
            cand = []
        if self.spec is not None and cand:
            # speculative path: draft + multi-token verify replaces the
            # one-token decode launch.  ``spec is None`` keeps the plain
            # path below byte-for-byte (greedy output is bit-identical
            # either way; only the launch count differs).
            self._spec_decode(cand, events, verify)
            self._c_events.inc(len(events))
            return events
        # materialise the page (maybe a fresh one) every running
        # sequence's next token will be written to -- evicting other
        # sequences under pressure -- THEN snapshot the table for the
        # device step.
        with phase("grow"):
            for slot, req in cand:
                if sched.slots[slot] is not req:
                    continue            # evicted by an earlier _grow
                try:
                    self._grow(slot, 1)
                except InjectedFault as e:
                    self._quarantine(req, e, events)
        running = [(s, r) for s, r in cand if sched.slots[s] is r]
        if serve.debug_invariants:
            self._check_invariants()
        if not running:
            self._c_events.inc(len(events))
            return events
        with phase("decode") as span:
            pos_np = np.zeros((serve.max_batch,), np.int32)
            ctx_tokens = 0
            for slot, _ in running:
                n = mgr.seq_len(slot)   # positions attended, new included
                pos_np[slot] = n - 1
                ctx_tokens += n
            span.args(rows=len(running), ctx_tokens=ctx_tokens)
            self._c_decode_launches.inc()
            self._c_decode_rows.inc(len(running))
            table = mgr.device_table()
            for slot, _ in sched.prefilling():
                # mid-prefill slots sit out the decode step: scratch-page
                # table row + pos 0, like idle slots (their real pages
                # must not see the decode step's writes)
                table[slot, :] = mgr.SCRATCH
            logits, self.pools = decode(
                self.params, jnp.asarray(self.next_tok), self.pools,
                jnp.asarray(table), jnp.asarray(pos_np))
        rowok = None
        if serve.logit_guard == "fail":
            with phase("guard"):
                # one device-side reduction + a max_batch-bool transfer:
                # the guard never pulls the full logits to host
                rowok = np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
        with phase("sample"):
            if all(r.sampling.greedy for _, r in running):
                # one batched argmax: the common all-greedy step costs one
                # device op, and matches the pre-core engine bit for bit
                toks = np.asarray(jnp.argmax(logits, axis=-1)
                                  .astype(jnp.int32))
                picked = {slot: int(toks[slot]) for slot, _ in running}
            else:
                # mixed sampling: one host sync, then per-row eager
                # sampling -- O(batch) small dispatches per step; a
                # batched vmapped sampler keyed on (temperature, top_k)
                # groups is the upgrade path
                logits_np = np.asarray(logits)
                picked = {slot: self._sample(req, logits_np[slot])
                          for slot, req in running}
        with phase("stream"):
            for slot, req in running:
                try:
                    self._fire("sample")
                    if rowok is not None and not rowok[slot]:
                        raise LogitError(
                            f"request {req.id}: non-finite logits at "
                            f"token {len(req.generated)}",
                            request_id=req.id)
                except (InjectedFault, RequestError) as e:
                    self._quarantine(req, e, events)
                    continue
                tok = picked[slot]
                req.generated.append(tok)
                self.next_tok[slot] = tok
                if self.tracer is not None:
                    self.tracer.on_token(req)
                self._stream(req, events)
        self._c_events.inc(len(events))
        return events

    # ------------------------------------------------------------------
    # speculative decode phase (serving/spec.py)
    # ------------------------------------------------------------------
    def _spec_decode(self, cand, events: List[StreamEvent],
                     verify) -> None:
        """One speculative step for every running slot: draft up to K
        continuation tokens from the request's own text, append them to
        the paged KV (COW-safe multi-token ``append``) and score all
        K+1 positions in ONE chunked paged-prefill launch, then keep
        the accepted prefix plus one correction/bonus token and
        ``truncate`` the rejected rows' KV exactly -- restoring the
        RUNNING invariant ``seq_len == len(prompt)+len(generated)-1``
        so prefix sharing, preemption/swap and quarantine compose
        unchanged.  The ``spec_verify`` fault site fires before any
        drafting; an injected fault degrades the step to K=0 (a
        one-token verify -- same tokens, strictly no speculation)."""
        sched, mgr, serve = self.sched, self.mgr, self.serve
        phase = self.phases
        width = serve.spec_tokens + 1
        with phase("verify"):
            try:
                self._fire("spec_verify")
                drafts = {}
                for slot, req in cand:
                    # cap K so a fully-accepted run plus its bonus token
                    # never overshoots max_new_tokens (also bounds page
                    # growth to what submit-time validation admitted)
                    cap = min(serve.spec_tokens,
                              req.max_new_tokens - len(req.generated) - 1)
                    drafts[slot] = (self.spec.propose(req)[:cap]
                                    if cap > 0 else [])
            except InjectedFault:
                drafts = {slot: [] for slot, _ in cand}
            with phase("grow"):
                for slot, req in cand:
                    if sched.slots[slot] is not req:
                        continue        # evicted by an earlier _grow
                    try:
                        self._grow(slot, 1 + len(drafts[slot]))
                    except InjectedFault as e:
                        self._quarantine(req, e, events)
            running = [(s, r) for s, r in cand if sched.slots[s] is r]
            if serve.debug_invariants:
                self._check_invariants()
            if not running:
                return
            # slot-indexed batch like the decode step, but every row NOT
            # in the verify batch gets a scratch table row: with
            # n_valid=0 it writes no K/V, so prefilling, done and idle
            # slots never see this launch
            buf = np.zeros((serve.max_batch, width), np.int32)
            table = np.full((serve.max_batch, mgr.max_pages_per_seq),
                            mgr.SCRATCH, np.int32)
            pos0 = np.zeros((serve.max_batch,), np.int32)
            nval = np.zeros((serve.max_batch,), np.int32)
            for slot, req in running:
                d = drafts[slot]
                buf[slot, 0] = self.next_tok[slot]
                if d:
                    buf[slot, 1:1 + len(d)] = d
                table[slot] = mgr.table[slot]
                pos0[slot] = mgr.seq_len(slot) - (1 + len(d))
                nval[slot] = 1 + len(d)
            self.spec_launches += 1
            self.pools, logits = verify(
                self.params, jnp.asarray(buf), self.pools,
                jnp.asarray(table), jnp.asarray(pos0), jnp.asarray(nval))
        rowok = None
        if serve.logit_guard == "fail":
            with phase("guard"):
                # (B, width) bools: acceptance guards each row only when
                # its logits are consumed, so K=0 matches the plain path
                # exactly
                rowok = np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
        survivors = []
        with phase("sample"):
            argm = np.asarray(jnp.argmax(logits, axis=-1)
                              .astype(jnp.int32))
            logits_np = (np.asarray(logits)
                         if any(not r.sampling.greedy for _, r in running)
                         else None)
            for slot, req in running:
                d = drafts[slot]
                sp = req.sampling
                old_len = int(pos0[slot])
                ok_row = rowok[slot] if rowok is not None else None
                try:
                    self._fire("sample")
                    if sp.greedy:
                        toks, acc = verify_greedy(
                            d, argm[slot], stop_ids=req.stop_token_ids,
                            budget=req.max_new_tokens - len(req.generated),
                            row_ok=ok_row, request_id=req.id,
                            n0=len(req.generated))
                    else:
                        toks, acc = verify_residual(
                            d, logits_np[slot], seed=sp.seed,
                            n0=len(req.generated),
                            temperature=sp.temperature, top_k=sp.top_k,
                            stop_ids=req.stop_token_ids,
                            budget=req.max_new_tokens - len(req.generated),
                            row_ok=ok_row, request_id=req.id)
                except (InjectedFault, RequestError) as e:
                    self._quarantine(req, e, events)
                    continue
                if d:
                    self.spec.observe(req.id, len(d), acc)
                    self._c_spec_drafted.inc(len(d))
                    self._c_spec_accepted.inc(acc)
                    self._h_spec_accept.observe(acc / len(d))
                    self._h_spec_run.observe(float(acc))
                for tok in toks:
                    req.generated.append(int(tok))
                    if self.tracer is not None:
                        self.tracer.on_token(req)
                self.next_tok[slot] = req.generated[-1]
                # exact rollback: drop the rejected drafts' KV rows and
                # the (never-written) row grown for the newest sampled
                # token
                mgr.truncate(slot, old_len + len(toks))
                survivors.append((slot, req))
        if serve.debug_invariants:
            self._check_invariants()
        with phase("stream"):
            for slot, req in survivors:
                self._stream(req, events)
