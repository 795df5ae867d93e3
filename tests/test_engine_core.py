"""EngineCore step API: persistent engine, per-request SamplingParams,
counter-based RNG, stop tokens, abort, and the generate_stream shim.

Unit level: SamplingParams normalisation and aliases, multi-stop /
stop-on-first-token semantics, scheduler.abort bookkeeping.  System
level: driving ``step()`` directly (add mid-flight, abort mid-prefill,
invariants every step, late request bit-identical to a solo run),
abort at every lifecycle stage without page leaks, sampled-token
reproducibility across batch compositions at temperature > 0, and the
deprecation contract of the engine-global sampling knobs.
"""
import warnings

import jax
import numpy as np
import pytest

from repro.config import ParallelConfig, ServeConfig, get_model_config, \
    reduce_for_smoke
from repro.serving.core import EngineCore, StreamEvent
from repro.serving.paged_cache import PagedKVCache
from repro.serving.scheduler import (ABORTED, FINISHED, PREFILLING, RUNNING,
                                     ContinuousBatchScheduler, Request,
                                     SamplingParams)


# ---------------------------------------------------------------------------
# unit: SamplingParams / Request aliases / stop tokens
# ---------------------------------------------------------------------------

def test_sampling_params_normalise_and_validate():
    sp = SamplingParams(stop_token_ids={7, 3, 7})
    assert sp.stop_token_ids == (3, 7)            # set -> sorted tuple
    assert sp.greedy                              # temperature 0 default
    assert not SamplingParams(temperature=0.5).greedy
    assert SamplingParams(temperature=0.5, top_k=1).greedy
    with pytest.raises(ValueError, match="max_new_tokens"):
        SamplingParams(max_new_tokens=0)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-1.0)


def test_request_aliases_fold_into_sampling():
    # eos_id joins the stop set, max_new_tokens= overrides
    r = Request(id=0, prompt=np.array([1, 2]), max_new_tokens=5, eos_id=9,
                sampling=SamplingParams(stop_token_ids=(4,)))
    assert r.sampling.stop_token_ids == (4, 9)
    assert r.max_new_tokens == r.sampling.max_new_tokens == 5
    # sampling alone drives length; aliases alone still work (legacy)
    r2 = Request(id=1, prompt=np.array([1]),
                 sampling=SamplingParams(max_new_tokens=3))
    assert r2.max_new_tokens == 3
    r3 = Request(id=2, prompt=np.array([1]), max_new_tokens=4, eos_id=7)
    assert r3.sampling is None and r3.stop_token_ids == (7,)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(id=3, prompt=np.array([1]))


def test_multi_stop_and_stop_on_first_token():
    r = Request(id=0, prompt=np.array([1, 2]),
                sampling=SamplingParams(max_new_tokens=100,
                                        stop_token_ids={7, 11}))
    assert not r.done
    r.generated = [3, 4]
    assert not r.done
    r.generated = [3, 11]                         # second stop id works
    assert r.done
    r.generated = [7]                             # stop on first token
    assert r.done
    r.generated = [3, 7, 5]                       # only the LAST counts
    assert not r.done


def test_eos_alias_still_finishes_early():
    r = Request(id=0, prompt=np.array([1, 2]), max_new_tokens=100,
                eos_id=7)
    r.generated = [3, 7]
    assert r.done


def test_scheduler_abort_releases_pages_and_cow_debt():
    cache = PagedKVCache(num_pages=8, page_size=4, max_slots=2,
                         max_pages_per_seq=4)
    sched = ContinuousBatchScheduler(cache)
    a, b = (Request(id=0, prompt=np.arange(4), max_new_tokens=4),
            Request(id=1, prompt=np.arange(4), max_new_tokens=4))
    sched.submit(a)
    sched.submit(b)
    sched.admit()
    # b shares a's partially-filled tail page, then COWs off it
    pages = cache.append(0, 2)
    cache.free(1)                                 # back to an empty slot
    cache.alloc(1)
    cache.share_pages(1, pages, 2)
    cache.append(1, 1)                            # COW: slot 1 moves
    assert cache.cow_pending
    free0 = cache.free_pages
    assert sched.abort(1) is b and b.state == ABORTED
    assert not cache.cow_pending                  # debt died with it
    assert cache.free_pages == free0 + 1          # its COW page came back
    assert sched.slots[1] is None
    cache.check_invariants()
    # unknown / repeated aborts are no-ops
    assert sched.abort(1) is None
    assert sched.abort(99) is None


# ---------------------------------------------------------------------------
# system fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def built():
    from repro.models import build_model
    cfg = reduce_for_smoke(get_model_config("gemma2-2b"))
    model = build_model(cfg, ParallelConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


def _core(built, **serve_kw):
    model, params, cfg = built
    serve_kw.setdefault("max_batch", 3)
    serve_kw.setdefault("max_seq_len", 96)
    serve_kw.setdefault("page_size", 16)
    serve_kw.setdefault("prefill_chunk", 16)
    serve_kw.setdefault("debug_invariants", True)
    return EngineCore(model, params, cfg,
                      ServeConfig(**serve_kw)), cfg


def _drain(core, ids=None):
    """step() until idle; returns {request_id: [tokens]} of the events."""
    out = {}
    while core.has_work:
        for ev in core.step():
            out.setdefault(ev.request_id, []).append(ev.token)
    if ids is not None:
        assert set(out) >= set(ids)
    return out


def _solo_tokens(core, prompt, sampling, rid=900):
    core.add_request(prompt, sampling, request_id=rid)
    return _drain(core)[rid]


# ---------------------------------------------------------------------------
# system: the step API end to end (the acceptance scenario)
# ---------------------------------------------------------------------------

def test_engine_core_step_api_end_to_end(built):
    """Add 3 requests, step a few times, add a 4th mid-flight, abort one
    mid-prefill, drain: invariants hold every step, events are
    well-formed, and the late request's tokens match a solo run."""
    core, cfg = _core(built, num_pages=13)
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, cfg.vocab_size, size=s)
               for i, s in enumerate((5, 40, 9, 12))}
    sp = SamplingParams(max_new_tokens=6)
    for i in range(3):
        assert core.add_request(prompts[i], sp) == i
    assert core.has_work and core.stats()["waiting"] == 3

    events = []
    for _ in range(2):
        events += core.step()
        core.mgr.check_invariants(
            extern_refs=core.prefix.page_refs() if core.prefix else None)
    # request 1 (40-token prompt, 16-token chunks under a 16-token
    # budget) is still prefilling after 2 steps; abort it mid-prefill
    assert core.requests[1].state == PREFILLING
    held = set(core.mgr.owned_pages(core.requests[1].slot))
    assert held, "mid-prefill victim held no pages"
    assert core.abort(1)
    assert not core.abort(1)                      # idempotent
    core.mgr.check_invariants()
    # a 4th request arrives mid-flight
    assert core.add_request(prompts[3], sp) == 3
    late = core.requests[3]
    while core.has_work:
        events += core.step()
        core.mgr.check_invariants(
            extern_refs=core.prefix.page_refs() if core.prefix else None)
    assert late.state == FINISHED
    assert core.mgr.used_pages == 0, "pages leaked after drain"

    by_req = {}
    for ev in events:
        by_req.setdefault(ev.request_id, []).append(ev)
    assert 1 not in by_req or len(by_req[1]) == 0  # aborted: no tokens
    for rid in (0, 2, 3):
        evs = by_req[rid]
        assert [e.index for e in evs] == list(range(6))
        assert [e.finished for e in evs] == [False] * 5 + [True]

    # the late request's tokens match a solo run on a fresh core
    solo, _ = _core(built, num_pages=13)
    assert _solo_tokens(solo, prompts[3], sp) == \
        [e.token for e in by_req[3]]


def test_abort_waiting_and_unknown(built):
    core, cfg = _core(built, num_pages=13)
    rng = np.random.default_rng(1)
    rid = core.add_request(rng.integers(0, cfg.vocab_size, size=4),
                           SamplingParams(max_new_tokens=3))
    assert core.abort(rid)                        # still WAITING
    assert not core.has_work
    assert not core.abort(rid) and not core.abort(12345)
    assert core.stats()["aborts"] == 1


def test_abort_mid_decode_frees_pages_for_reuse(built):
    core, cfg = _core(built, num_pages=13)
    rng = np.random.default_rng(2)
    rid = core.add_request(rng.integers(0, cfg.vocab_size, size=20),
                           SamplingParams(max_new_tokens=40))
    while core.requests[rid].state != RUNNING:
        core.step()
    for _ in range(2):
        core.step()                               # a few decode tokens
    held = set(core.mgr.owned_pages(core.requests[rid].slot))
    assert held
    assert core.abort(rid)
    core.mgr.check_invariants()
    assert core.mgr.used_pages == 0
    # a subsequent request reuses the freed physical pages (LIFO list)
    rid2 = core.add_request(rng.integers(0, cfg.vocab_size, size=20),
                            SamplingParams(max_new_tokens=2))
    core.step()                                   # admit + first chunk
    req2 = core.requests[rid2]
    assert set(core.mgr.owned_pages(req2.slot)) & held, \
        "freed pages not reused"
    _drain(core)
    assert req2.state == FINISHED
    assert core.mgr.used_pages == 0


def test_abort_while_swap_preempted_drops_stash(built):
    """Force a swap preemption, then abort the victim while it waits in
    the resuming queue: the host stash is dropped, nothing leaks, and
    the surviving request still finishes."""
    core, cfg = _core(built, num_pages=7, preempt_policy="swap",
                      max_batch=2)
    rng = np.random.default_rng(3)
    a = core.add_request(rng.integers(0, cfg.vocab_size, size=8),
                         SamplingParams(max_new_tokens=60))
    b = core.add_request(rng.integers(0, cfg.vocab_size, size=8),
                         SamplingParams(max_new_tokens=60))
    while core.pressure.stats["swaps"] == 0:
        assert core.has_work
        core.step()
    victim = next(r.id for r in core.sched.resuming)
    assert core.pressure.holds(victim)
    assert core.abort(victim)
    assert not core.pressure.holds(victim)
    assert core.pressure.stats["abort_drops"] == 1
    core.mgr.check_invariants()
    _drain(core)
    survivor = a if victim == b else b
    req = next(r for r in core.sched.finished if r.id == survivor)
    assert req.state == FINISHED and len(req.generated) == 60
    assert len(core.pressure.host_pool) == 0, "stash leaked"
    assert core.mgr.used_pages == 0


def test_abort_while_holding_shared_prefix_pages(built):
    """Aborting a request that shares radix-cached prefix pages only
    drops its references: the index keeps the pages, refcounts balance
    (extern-aware invariants), and a later request still hits them."""
    core, cfg = _core(built, prefix_cache=True)
    rng = np.random.default_rng(4)
    sys_prompt = rng.integers(0, cfg.vocab_size, size=32)   # 2 pages

    def make_prompt(n):
        return np.concatenate(
            [sys_prompt, rng.integers(0, cfg.vocab_size, size=n)])

    sp = SamplingParams(max_new_tokens=4)
    core.add_request(make_prompt(5), sp, request_id=0)
    _drain(core)                                   # seed the index
    rid = core.add_request(make_prompt(6), sp, request_id=1)
    core.step()                                    # admitted + sharing
    req = core.requests[rid]
    assert req.matched_len == 32, "prefix not shared"
    shared = set(core.mgr.owned_pages(req.slot)[:2])
    assert all(core.mgr.refcount(p) >= 2 for p in shared)
    assert core.abort(rid)
    core.mgr.check_invariants(extern_refs=core.prefix.page_refs())
    # the index still holds the shared pages for the next request
    assert all(core.mgr.refcount(p) == 1 for p in shared)
    rid3 = core.add_request(make_prompt(7), sp, request_id=2)
    core.step()
    assert core.requests[rid3].matched_len == 32
    _drain(core)
    core.mgr.check_invariants(extern_refs=core.prefix.page_refs())


def test_reset_clears_everything(built):
    core, cfg = _core(built, prefix_cache=True)
    rng = np.random.default_rng(5)
    core.add_request(rng.integers(0, cfg.vocab_size, size=20),
                     SamplingParams(max_new_tokens=4))
    _drain(core)
    assert core.prefix.cached_pages > 0
    core.reset()
    assert not core.has_work
    assert core.mgr.used_pages == 0 and core.prefix.cached_pages == 0
    assert core.stats()["finished"] == 0
    # serves normally after the reset
    rid = core.add_request(rng.integers(0, cfg.vocab_size, size=8),
                           SamplingParams(max_new_tokens=3))
    assert len(_drain(core)[rid]) == 3


# ---------------------------------------------------------------------------
# system: per-request counter-based RNG
# ---------------------------------------------------------------------------

def test_sampled_tokens_invariant_to_batch_composition(built):
    """temperature > 0: a request's sampled tokens depend only on its
    prompt and SamplingParams.seed -- not on co-tenants, admission
    order, or preemption pressure around it."""
    sp = SamplingParams(temperature=0.7, top_k=8, seed=123,
                        max_new_tokens=8)
    rng = np.random.default_rng(6)
    _, _, cfg = built
    prompt = rng.integers(0, cfg.vocab_size, size=9)

    solo, _ = _core(built)
    alone = _solo_tokens(solo, prompt, sp)
    assert len(alone) == 8

    # same request mixed into a busy engine (greedy + other seeded
    # co-tenants, a long prompt prefilling, and an undersized pool
    # forcing preemptions)
    busy, _ = _core(built, num_pages=9, preempt_policy="swap")
    busy.add_request(rng.integers(0, cfg.vocab_size, size=40),
                     SamplingParams(max_new_tokens=10), request_id=50)
    busy.add_request(rng.integers(0, cfg.vocab_size, size=4),
                     SamplingParams(temperature=0.9, seed=7,
                                    max_new_tokens=20), request_id=51)
    busy.step()
    rid = busy.add_request(prompt, sp)             # arrives mid-flight
    mixed = _drain(busy)[rid]
    assert mixed == alone
    # identical seed + prompt on the same engine reproduces again
    assert _solo_tokens(busy, prompt, sp) == alone


def test_distinct_seeds_give_distinct_streams(built):
    core, cfg = _core(built)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, size=6)
    a = _solo_tokens(core, prompt,
                     SamplingParams(temperature=1.0, seed=1,
                                    max_new_tokens=12), rid=0)
    b = _solo_tokens(core, prompt,
                     SamplingParams(temperature=1.0, seed=2,
                                    max_new_tokens=12), rid=1)
    assert a != b


def test_stop_token_ends_generation_in_engine(built):
    """A stop id sampled mid-stream finishes the request early, and a
    stop on the very first token yields exactly one event."""
    core, cfg = _core(built)
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab_size, size=7)
    greedy = _solo_tokens(core, prompt,
                          SamplingParams(max_new_tokens=8), rid=0)
    # stop on a mid-stream greedy token: the stream truncates at that
    # token's FIRST occurrence (the tiny model may repeat tokens)
    stop_tok = greedy[2]
    rid = core.add_request(prompt, SamplingParams(
        max_new_tokens=8, stop_token_ids={stop_tok}), request_id=1)
    toks = _drain(core)[rid]
    assert toks == greedy[:greedy.index(stop_tok) + 1]
    req = next(r for r in core.sched.finished if r.id == rid)
    assert req.state == FINISHED
    # stop on the first token
    rid = core.add_request(prompt, SamplingParams(
        max_new_tokens=8, stop_token_ids={greedy[0], 100000}),
        request_id=2)
    evs = []
    while core.has_work:
        evs += core.step()
    evs = [e for e in evs if e.request_id == rid]
    assert len(evs) == 1 and evs[0].finished
    assert evs[0].token == greedy[0]


# ---------------------------------------------------------------------------
# system: generate_stream is a thin shim over the core
# ---------------------------------------------------------------------------

def test_generate_stream_matches_core_and_persists(built):
    """The wrapper's greedy events are exactly what driving the core by
    hand produces, and both run on the same persistent state."""
    from repro.serving.engine import ServeEngine
    model, params, cfg = built
    serve = ServeConfig(max_batch=3, max_seq_len=96, page_size=16,
                        prefill_chunk=16, debug_invariants=True)
    engine = ServeEngine(model=model, params=params, cfg=cfg, serve=serve)
    rng = np.random.default_rng(9)
    spec = [(5, 6), (23, 3), (9, 4)]
    reqs = [Request(id=i, prompt=rng.integers(0, cfg.vocab_size, size=s),
                    sampling=SamplingParams(max_new_tokens=n))
            for i, (s, n) in enumerate(spec)]
    events = list(engine.generate_stream(reqs))
    assert engine.core.steps > 0                   # same core underneath

    core, _ = _core(built)
    for i, (s, n) in enumerate(spec):
        core.add_request(reqs[i].prompt,
                         SamplingParams(max_new_tokens=n), request_id=i)
    direct = []
    while core.has_work:
        direct += core.step()
    assert [tuple(e) for e in events] == [tuple(e) for e in direct]
    assert isinstance(direct[0], StreamEvent)


def test_interleaved_streams_route_all_events(built):
    """Two generate_stream calls advanced alternately share the one
    persistent core: a step driven by either drain may produce the
    other's tokens, which must be buffered and delivered -- not
    dropped."""
    from repro.serving.engine import ServeEngine
    model, params, cfg = built
    serve = ServeConfig(max_batch=3, max_seq_len=96, page_size=16,
                        prefill_chunk=16, debug_invariants=True)
    engine = ServeEngine(model=model, params=params, cfg=cfg, serve=serve)
    rng = np.random.default_rng(13)
    r1 = Request(id=0, prompt=rng.integers(0, cfg.vocab_size, size=6),
                 sampling=SamplingParams(max_new_tokens=7))
    r2 = Request(id=10, prompt=rng.integers(0, cfg.vocab_size, size=9),
                 sampling=SamplingParams(max_new_tokens=5))
    g1 = engine.generate_stream([r1])
    g2 = engine.generate_stream([r2])
    got1, got2 = [], []
    alive1 = alive2 = True
    while alive1 or alive2:                       # strict alternation
        if alive1:
            try:
                got1.append(next(g1))
            except StopIteration:
                alive1 = False
        if alive2:
            try:
                got2.append(next(g2))
            except StopIteration:
                alive2 = False
    assert [e.index for e in got1] == list(range(7))
    assert [e.index for e in got2] == list(range(5))
    assert [e.token for e in got1] == r1.generated
    assert [e.token for e in got2] == r2.generated
    assert engine.last_cache.used_pages == 0
    # each stream matches its solo oracle (greedy)
    core, _ = _core(built)
    assert _solo_tokens(core, r1.prompt, SamplingParams(max_new_tokens=7),
                        rid=0) == r1.generated
    assert _solo_tokens(core, r2.prompt, SamplingParams(max_new_tokens=5),
                        rid=1) == r2.generated


def test_never_started_stream_cleans_up(built):
    """Dropping a generate_stream iterator before its first next() must
    still abort the call's queued requests and unregister its routing
    entry -- they already live on the persistent core."""
    import gc
    from repro.serving.engine import ServeEngine
    model, params, cfg = built
    engine = ServeEngine(model=model, params=params, cfg=cfg,
                         serve=ServeConfig(max_batch=2, max_seq_len=64,
                                           page_size=16))
    rng = np.random.default_rng(15)
    req = Request(id=0, prompt=rng.integers(0, cfg.vocab_size, size=5),
                  sampling=SamplingParams(max_new_tokens=4))
    gen = engine.generate_stream([req])
    assert engine.core.stats()["waiting"] == 1
    del gen
    gc.collect()
    assert not engine.core.has_work
    assert req.state == ABORTED
    assert engine._stream_subs == []
    # the engine serves normally afterwards
    again = Request(id=1, prompt=req.prompt.copy(),
                    sampling=SamplingParams(max_new_tokens=4))
    assert len(list(engine.generate_stream([again]))) == 4


def test_direct_request_events_survive_wrapper_steps(built):
    """A direct add_request sharing the core with a generate_stream
    drain: the drain's steps may produce the direct request's tokens --
    they land in core.orphan_events instead of vanishing."""
    from repro.serving.engine import ServeEngine
    model, params, cfg = built
    engine = ServeEngine(model=model, params=params, cfg=cfg,
                         serve=ServeConfig(max_batch=3, max_seq_len=96,
                                           page_size=16,
                                           prefill_chunk=16))
    rng = np.random.default_rng(16)
    rid = engine.core.add_request(
        rng.integers(0, cfg.vocab_size, size=5),
        SamplingParams(max_new_tokens=4), request_id=77)
    stream_req = Request(id=0,
                         prompt=rng.integers(0, cfg.vocab_size, size=6),
                         sampling=SamplingParams(max_new_tokens=10))
    list(engine.generate_stream([stream_req]))
    # finish anything the wrapper left running, collecting directly
    direct = []
    while engine.core.has_work:
        direct += engine.core.step()
    mine = [e for e in engine.core.orphan_events
            if e.request_id == rid] + [e for e in direct
                                       if e.request_id == rid]
    done = next(r for r in engine.core.sched.finished if r.id == rid)
    assert [e.token for e in mine] == done.generated
    assert [e.index for e in mine] == list(range(4))


def test_add_request_aliases_stay_greedy(built):
    """The NEW API never inherits the deprecated engine-global knobs:
    add_request with only the legacy aliases gets the greedy default
    SamplingParams, even on a config whose global knobs would sample."""
    core, cfg = _core(built, temperature=1.0, top_k=0)
    rng = np.random.default_rng(14)
    prompt = rng.integers(0, cfg.vocab_size, size=6)
    rid = core.add_request(prompt, max_new_tokens=5, eos_id=100000)
    req = core.requests[rid]
    assert req.sampling.greedy
    assert req.sampling.stop_token_ids == (100000,)
    toks = _drain(core)[rid]
    rid2 = core.add_request(prompt, SamplingParams(max_new_tokens=5))
    assert _drain(core)[rid2] == toks             # bit-identical greedy


def test_abandoned_stream_aborts_without_prefix_cache(built):
    """Abandoning generate_stream mid-run aborts this call's requests on
    the (now unconditionally persistent) core -- no pages leak and the
    next call serves normally, prefix cache or not."""
    from repro.serving.engine import ServeEngine
    model, params, cfg = built
    serve = ServeConfig(max_batch=2, max_seq_len=96, page_size=16,
                        prefill_chunk=16, debug_invariants=True)
    engine = ServeEngine(model=model, params=params, cfg=cfg, serve=serve)
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, cfg.vocab_size, size=20)
    reqs = [Request(id=i, prompt=prompt.copy(),
                    sampling=SamplingParams(max_new_tokens=8))
            for i in range(2)]
    for ev in engine.generate_stream(reqs):
        break                                      # client disconnect
    mgr = engine.last_cache
    assert mgr.used_pages == 0 and not mgr.cow_pending
    assert engine.core.stats()["aborts"] >= 1
    mgr.check_invariants()
    again = Request(id=9, prompt=prompt.copy(),
                    sampling=SamplingParams(max_new_tokens=8))
    ev_tokens = [e.token for e in engine.generate_stream([again])]
    assert len(ev_tokens) == 8 and again.state == FINISHED


# ---------------------------------------------------------------------------
# deprecation contract of the engine-global knobs
# ---------------------------------------------------------------------------

def test_supported_path_emits_no_deprecation_warning(built):
    """Requests carrying SamplingParams never trip the legacy warning,
    even on a ServeConfig that left the old knobs at their defaults."""
    from repro.serving.engine import ServeEngine
    model, params, cfg = built
    engine = ServeEngine(model=model, params=params, cfg=cfg,
                         serve=ServeConfig(max_batch=2, max_seq_len=64,
                                           page_size=16))
    rng = np.random.default_rng(11)
    req = Request(id=0, prompt=rng.integers(0, cfg.vocab_size, size=5),
                  sampling=SamplingParams(max_new_tokens=4))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        list(engine.generate_stream([req]))
    assert req.state == FINISHED


def test_legacy_global_knobs_warn_exactly_once(built):
    """Params-less requests inheriting a changed engine-global
    temperature/top_k warn once per core -- not per request."""
    from repro.serving.engine import ServeEngine
    model, params, cfg = built
    engine = ServeEngine(model=model, params=params, cfg=cfg,
                         serve=ServeConfig(max_batch=2, max_seq_len=64,
                                           page_size=16, top_k=1))
    rng = np.random.default_rng(12)
    reqs = [Request(id=i, prompt=rng.integers(0, cfg.vocab_size, size=5),
                    max_new_tokens=3) for i in range(2)]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        list(engine.generate_stream(reqs))
        more = [Request(id=5, prompt=rng.integers(0, cfg.vocab_size,
                                                  size=4),
                        max_new_tokens=2)]
        list(engine.generate_stream(more))
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)
           and "temperature/top_k" in str(w.message)]
    assert len(dep) == 1
    # the resolved legacy params are greedy (top_k=1), so tokens match
    # the explicit-params path bit for bit
    sp_req = Request(id=7, prompt=reqs[0].prompt.copy(),
                     sampling=SamplingParams(max_new_tokens=3))
    list(engine.generate_stream([sp_req]))
    assert sp_req.generated == reqs[0].generated


def test_orphan_event_drops_are_counted(built):
    """The orphan-event buffer is bounded (dropping the oldest is the
    point), but drops must not be silent: stats() reports how many
    orphaned events were lost past the 4096-entry window."""
    from repro.serving.core import StreamEvent
    core, _ = _core(built, num_pages=13)
    assert core.stats()["orphans_dropped"] == 0
    cap = core.orphan_events.maxlen
    for i in range(cap + 7):
        core.orphan_events.append(StreamEvent(0, i, i, False))
    st = core.stats()
    assert len(core.orphan_events) == cap
    assert st["orphan_events_pending"] == cap
    assert st["orphans_dropped"] == 7
    # the oldest 7 fell off the window; the newest survive in order
    assert core.orphan_events[0].token == 7
    assert core.orphan_events[-1].token == cap + 6
    # reset() starts a fresh buffer and counter
    core.reset()
    assert core.stats()["orphans_dropped"] == 0


# ---------------------------------------------------------------------------
# fault-tolerance satellites: structured rejection, abort idempotency,
# stop strings
# ---------------------------------------------------------------------------

def test_submit_time_structured_rejection(built):
    """A request that can never fit is rejected at submission with a
    structured RequestRejected (a ValueError, so legacy callers keep
    working) -- never a RuntimeError out of a later step().  The engine
    stays clean and keeps serving."""
    from repro.serving.faults import RequestRejected
    core, cfg = _core(built, num_pages=3)         # 2 usable pages = 32 tok
    rng = np.random.default_rng(40)
    sp = SamplingParams(max_new_tokens=6)
    with pytest.raises(RequestRejected, match="pool has 2") as ei:
        core.add_request(rng.integers(0, cfg.vocab_size, size=40),
                         SamplingParams(max_new_tokens=6), request_id=0)
    assert isinstance(ei.value, ValueError)
    assert ei.value.code == "rejected" and ei.value.request_id == 0
    with pytest.raises(RequestRejected, match="max_seq_len"):
        core.add_request(rng.integers(0, cfg.vocab_size, size=90),
                         SamplingParams(max_new_tokens=30), request_id=1)
    assert not core.requests and not core.has_work
    rid = core.add_request(rng.integers(0, cfg.vocab_size, size=5), sp)
    assert len(_drain(core)[rid]) == 6            # engine unpoisoned


def test_double_abort_has_no_side_effects(built):
    """Aborting twice (or aborting a finished id) must not double-free
    pages, double-count aborts, or disturb a co-tenant."""
    core, cfg = _core(built, num_pages=13)
    rng = np.random.default_rng(41)
    sp = SamplingParams(max_new_tokens=6)
    core.add_request(rng.integers(0, cfg.vocab_size, size=8), sp,
                     request_id=0)
    core.add_request(rng.integers(0, cfg.vocab_size, size=8), sp,
                     request_id=1)
    survivor = core.requests[1]
    core.step()
    assert core.abort(0)
    free = core.mgr.free_pages
    for _ in range(3):
        assert not core.abort(0)                  # idempotent, no effect
    assert core.mgr.free_pages == free
    assert core.aborts == 1
    core.mgr.check_invariants()
    _drain(core)
    assert survivor.state == FINISHED and len(survivor.generated) == 6
    assert not core.abort(1)                      # finished: no-op too
    assert core.aborts == 1 and core.mgr.used_pages == 0


def _detok(tokens):
    """Deterministic test detokenizer: token t -> "<t>"."""
    return "".join(f"<{int(t)}>" for t in tokens)


def test_stop_strings_trim_and_span_token_boundary(built):
    """A stop string spanning a token boundary: only tokens wholly
    before the match are ever emitted (the matcher holds back any text
    suffix that could still become a match), the matched suffix is
    trimmed, and the stream ends with a kind="stop" event naming the
    matched string."""
    model, params, cfg = built
    serve = ServeConfig(max_batch=3, max_seq_len=96, page_size=16,
                        prefill_chunk=16, debug_invariants=True,
                        num_pages=13)
    sp = SamplingParams(max_new_tokens=6)

    def boundary_stop(want):
        """A stop string crossing the boundary between generated tokens
        k and k+1 (the tail of piece k plus the head of piece k+1): the
        first boundary whose string first occurs after piece 0, or None
        -- a greedy stream that repeats one token matches inside piece 0
        at every boundary, and then nothing would be emitted."""
        pieces = [f"<{t}>" for t in want]
        text = "".join(pieces)
        ends = np.cumsum([len(p) for p in pieces])
        for k in range(len(pieces) - 1):
            stop = pieces[k][-2:] + pieces[k + 1][:2]
            match = text.find(stop)
            if match >= ends[0]:
                return stop, int((ends <= match).sum())
        return None

    # the random smoke model often repeats one token: take the first
    # prompt whose greedy stream has a usable boundary
    for seed in range(42, 74):
        prompt = np.random.default_rng(seed).integers(0, cfg.vocab_size,
                                                      size=7)
        plain = EngineCore(model, params, cfg, serve, detokenize=_detok)
        want = _solo_tokens(plain, prompt, sp)
        assert len(want) == 6
        found = boundary_stop(want)
        if found is not None:
            break
    else:
        pytest.fail("no prompt gave a greedy stream with a boundary stop "
                    "string after piece 0")
    stop, exp_emitted = found
    assert 0 < exp_emitted < len(want)            # genuinely mid-stream

    core = EngineCore(model, params, cfg, serve, detokenize=_detok)
    core.add_request(prompt, SamplingParams(max_new_tokens=6,
                                            stop_strings=(stop,)),
                     request_id=0)
    req = core.requests[0]
    events = []
    while core.has_work:
        events += core.step()
    assert req.state == FINISHED and req.stop_matched
    toks = [e for e in events if e.kind == "token"]
    stops = [e for e in events if e.kind == "stop"]
    assert [e.token for e in toks] == want[:exp_emitted]
    assert not toks or not toks[-1].finished      # stop event terminates
    assert len(stops) == 1
    assert stops[0].finished and stops[0].token == -1
    assert stops[0].detail == stop
    assert core.mgr.used_pages == 0
    assert not core._stop_state                   # holdback state freed


def test_stop_strings_holdback_then_flush(built):
    """A stop-string *prefix* at the text tail is held back (never
    half-emit a potential match) but flushed in order when the request
    finishes by length instead."""
    model, params, cfg = built
    serve = ServeConfig(max_batch=3, max_seq_len=96, page_size=16,
                        prefill_chunk=16, debug_invariants=True,
                        num_pages=13)
    rng = np.random.default_rng(43)
    prompt = rng.integers(0, cfg.vocab_size, size=5)
    sp = SamplingParams(max_new_tokens=5)
    plain = EngineCore(model, params, cfg, serve, detokenize=_detok)
    want = _solo_tokens(plain, prompt, sp)
    # piece 2 is a proper prefix of the stop string, which never
    # completes: token 2 must be held while the request is live
    stop = f"<{want[2]}>" + "§never"

    core = EngineCore(model, params, cfg, serve, detokenize=_detok)
    core.add_request(prompt, SamplingParams(max_new_tokens=5,
                                            stop_strings=(stop,)),
                     request_id=0)
    req = core.requests[0]
    held_seen = False
    events = []
    while core.has_work:
        events += core.step()
        if len(req.generated) == 3 and not req.done:
            assert req.emitted == 2, "potential match was half-emitted"
            held_seen = True
    assert held_seen
    toks = [e for e in events if e.kind == "token"]
    assert [e.token for e in toks] == want        # flushed, bit-identical
    assert [e.index for e in toks] == list(range(5))
    assert toks[-1].finished and req.state == FINISHED
    assert not any(e.kind == "stop" for e in events)


def test_stop_strings_require_detokenizer(built):
    from repro.serving.faults import RequestRejected
    core, cfg = _core(built, num_pages=13)        # no detokenize=
    rng = np.random.default_rng(44)
    with pytest.raises(RequestRejected, match="detokenize"):
        core.add_request(rng.integers(0, cfg.vocab_size, size=5),
                         SamplingParams(max_new_tokens=3,
                                        stop_strings=("x",)))
    assert not core.has_work


def test_stop_strings_validation():
    sp = SamplingParams(stop_strings=["ab", "ab", "c"])
    assert sp.stop_strings == ("ab", "c")         # deduped, order kept
    with pytest.raises(ValueError, match="stop_strings"):
        SamplingParams(stop_strings=("ok", ""))
