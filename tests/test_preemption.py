"""Preemptible serving under pressure: PagePool park/unpark/drop_parked in
isolation, slot preemption with KV swap-to-host (park-hit resume) and
without a host tier (re-prefill resume) — token-identical both ways, zero
leaked pages on both tiers, one serve-path trace — plus the pressure-facing
API surface: typed ``RequestTooLarge`` / ``EngineOverloaded`` on submit,
``deadline_ticks`` expiry (queued and live) raising ``DeadlineExceeded``
with partial output attached, ``result(timeout_ticks=)`` bounding the
drain, and the ``preempt_order`` policy hook (default order, SLO
interactive exemption).  Fault-injection chaos runs live in
tests/test_chaos.py."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.errors import (Cancelled, DeadlineExceeded,
                                EngineOverloaded, RequestTooLarge,
                                ServeError)
from repro.serve.handle import Request
from repro.serve.pool import PagePool
from repro.serve.scheduler import (ClassThenFamilyScheduler, EngineView,
                                   Scheduler, SloScheduler)

KEY = jax.random.PRNGKey(0)
CACHE = 64


@pytest.fixture(scope="module")
def qwen():
    # float32 keeps greedy argmax stable across batching layouts
    cfg = get_config("qwen2-1.5b", smoke=True).replace(dtype="float32")
    params = M.init_params(KEY, cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, L) for L in lens]


def _engine(params, cfg, **kw):
    kw.setdefault("batch_size", 1)
    kw.setdefault("cache_len", CACHE)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("token_budget", 32)
    return ServeEngine(params, cfg, **kw)


def _leak_free(eng):
    pool = eng.pool
    return bool((eng._ref == 0).all()
                and eng.reclaimable_pages == eng.n_pages
                and pool.parked_pages == 0
                and len(pool._host_free) + pool.host_cached_pages
                == pool.host_pages
                and set(eng._host_store) == set(pool._host_node))


# ---------------------------------------------------------------------------
# PagePool park / unpark / drop_parked (no engine, pure host bookkeeping)


def test_pool_park_moves_private_pages_to_host():
    pool = PagePool(4, 4, host_pages=4)
    pages = pool.alloc(3)
    slots = pool.park(pages)
    assert slots is not None and len(slots) == 3
    assert pool.parked_pages == 3
    assert pool.free_pages == 4  # parked pages left the device pool
    assert all(pool.ref(p) == 0 for p in pages)
    evs = pool.drain_events()
    assert [e[0] for e in evs] == ["demote"] * 3
    assert [e[1] for e in evs] == list(pages)
    assert pool.stats["park_demotions"] == 3


def test_pool_park_all_or_nothing_when_tier_small_or_absent():
    pool = PagePool(4, 4, host_pages=2)
    pages = pool.alloc(3)
    assert pool.park(pages) is None  # 3 pages, 2 host slots: refuse whole
    assert pool.parked_pages == 0
    assert pool.free_pages == 1  # pages still owned by the caller
    assert pool.drain_events() == []
    untiered = PagePool(4, 4, host_pages=0)
    assert untiered.park(untiered.alloc(1)) is None


def test_pool_unpark_allocates_and_promotes():
    pool = PagePool(4, 4, host_pages=4)
    slots = pool.park(pool.alloc(2))
    pool.drain_events()
    devs = pool.unpark(slots)
    assert len(devs) == 2
    assert pool.parked_pages == 0
    assert all(pool.ref(p) == 1 for p in devs)
    evs = pool.drain_events()
    assert [(e[0], e[1]) for e in evs] == [("promote", s) for s in slots]
    assert pool.stats["park_promotions"] == 2
    assert sorted(pool._host_free) == list(range(4))


def test_pool_drop_parked_frees_slots_with_hevict():
    pool = PagePool(4, 4, host_pages=4)
    slots = pool.park(pool.alloc(2))
    pool.drain_events()
    pool.drop_parked(slots)
    assert pool.parked_pages == 0
    assert sorted(pool._host_free) == list(range(4))
    assert [e[0] for e in pool.drain_events()] == ["hevict"] * 2
    assert pool.stats["parks_dropped"] == 2


def test_pool_storm_spares_parked_slots():
    pool = PagePool(8, 2, host_pages=8)
    slots = pool.park(pool.alloc(2))
    # a cached (trie-indexed, refcount-0) host page: park a prefix through
    # the normal demote path by filling and releasing an indexed chain
    node, pages, matched, cow = pool.match_prefix(np.arange(4))
    (pg,) = pool.alloc(1)
    node = pool.index_page(node, tuple(range(2)), pg)
    pool.release([pg])
    assert pool.evict_one()  # demotes the cached page to the host tier
    assert pool.host_cached_pages == 1
    n = pool.storm_host_cache()
    assert n == 1  # the cache entry died ...
    assert pool.host_cached_pages == 0
    assert pool.parked_pages == 2  # ... the parked live state survived
    assert sorted(pool._parked) == sorted(slots)


# ---------------------------------------------------------------------------
# Preempt / resume through the engine: token identity on both resume paths


def _overload_run(params, cfg, *, host_pages, preempt=True, scheduler="slo"):
    """One hog fills the only slot and the whole pool; an interactive chat
    arrives mid-decode.  Returns (engine, hog transcript, chat transcript)."""
    hog, chat = _prompts(cfg, [16, 6])
    eng = _engine(params, cfg, max_pages=4, host_pages=host_pages,
                  scheduler=scheduler, preempt=preempt)
    h_hog = eng.submit(hog, max_tokens=16)
    for _ in range(4):  # prefill + a few decode ticks
        eng.tick()
    assert len(h_hog.request.out_tokens) >= 1
    h_chat = eng.submit(chat, max_tokens=3, priority=1)
    res = eng.run()
    return eng, res[h_hog], res[h_chat]


def _solo_transcripts(params, cfg):
    hog, chat = _prompts(cfg, [16, 6])
    eng = _engine(params, cfg, batch_size=2, max_pages=16)
    uids = [eng.submit(hog, max_tokens=16),
            eng.submit(chat, max_tokens=3, priority=1)]
    res = eng.run()
    return res[uids[0]], res[uids[1]]


def test_preempt_resume_park_hit_token_identical(qwen):
    cfg, params = qwen
    want_hog, want_chat = _solo_transcripts(params, cfg)
    eng, got_hog, got_chat = _overload_run(params, cfg, host_pages=6)
    assert eng.stats["preemptions"] == 1
    assert eng.stats["resumes"] == 1
    assert eng.stats["resume_park_hits"] == 1
    assert eng.stats["resume_reprefills"] == 0
    assert eng.stats["preempt_pages_parked"] >= 1
    assert (got_hog, got_chat) == (want_hog, want_chat)
    assert eng.stats["traces"] == 1
    assert _leak_free(eng)


def test_preempt_resume_reprefill_token_identical(qwen):
    cfg, params = qwen
    want_hog, want_chat = _solo_transcripts(params, cfg)
    # no host tier: the victim's generated KV cannot park; resume replays
    # prompt + generated history through prefill instead
    eng, got_hog, got_chat = _overload_run(params, cfg, host_pages=0)
    assert eng.stats["preemptions"] == 1
    assert eng.stats["resumes"] == 1
    assert eng.stats["resume_reprefills"] == 1
    assert eng.stats["resume_park_hits"] == 0
    assert (got_hog, got_chat) == (want_hog, want_chat)
    assert eng.stats["traces"] == 1
    assert _leak_free(eng)


@pytest.mark.parametrize("host_pages", [6, 0], ids=["park-hit", "reprefill"])
def test_preempted_request_keeps_its_first_admission_stamp(qwen, host_pages):
    cfg, params = qwen
    hog, chat = _prompts(cfg, [16, 6])
    eng = _engine(params, cfg, max_pages=4, host_pages=host_pages,
                  scheduler="slo")
    h_hog = eng.submit(hog, max_tokens=16)
    for _ in range(4):
        eng.tick()
    first = h_hog.request.admitted_at
    h_chat = eng.submit(chat, max_tokens=3, priority=1)
    eng.run()
    assert eng.stats["preemptions"] == 1 and eng.stats["resumes"] == 1
    assert h_hog.request.admitted_at == first
    for h in (h_hog, h_chat):
        r = h.request
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
    assert h_hog.request.first_token_at < h_chat.request.admitted_at


def test_preempt_off_stalls_instead(qwen):
    cfg, params = qwen
    eng, got_hog, got_chat = _overload_run(params, cfg, host_pages=6,
                                           preempt=False)
    assert eng.stats["preemptions"] == 0
    want_hog, want_chat = _solo_transcripts(params, cfg)
    assert (got_hog, got_chat) == (want_hog, want_chat)  # just later
    assert _leak_free(eng)


@pytest.mark.parametrize("preempt", [True, False], ids=["preempt", "stall"])
def test_preemption_meets_a_deadline_that_stalling_misses(qwen, preempt):
    """An interactive chat with a tight deadline arrives while a hog holds
    the only slot and the whole pool: preemption serves it inside its
    deadline, exactly; stalling queues it behind the hog until it expires
    un-served.  Counted in ticks, so it holds on any backend."""
    cfg, params = qwen
    want_hog, want_chat = _solo_transcripts(params, cfg)
    hog, chat = _prompts(cfg, [16, 6])
    eng = _engine(params, cfg, max_pages=4, host_pages=6, scheduler="slo",
                  preempt=preempt)
    h_hog = eng.submit(hog, max_tokens=16)
    for _ in range(4):
        eng.tick()
    h_chat = eng.submit(chat, max_tokens=3, priority=1, deadline_ticks=6)
    res = eng.run()
    assert res[h_hog] == want_hog
    if preempt:
        assert h_chat.result() == want_chat
        assert eng.stats["deadline_expired"] == 0
    else:
        with pytest.raises(DeadlineExceeded) as exc:
            h_chat.result()
        assert exc.value.tokens == []
        assert eng.stats["deadline_expired"] == 1
    assert eng.stats["traces"] == 1
    assert _leak_free(eng)


def test_equal_priority_never_preempts(qwen):
    cfg, params = qwen
    # strict-priority guard: a same-class backlog waits, it never thrashes
    eng = _engine(params, cfg, max_pages=4, host_pages=6)
    for p in _prompts(cfg, [16, 16, 16]):
        eng.submit(p, max_tokens=8)
    eng.run()
    assert eng.stats["preemptions"] == 0
    assert _leak_free(eng)


# ---------------------------------------------------------------------------
# Typed submit errors and deadlines


def test_submit_too_large_raises_typed(qwen):
    cfg, params = qwen
    eng = _engine(params, cfg, max_pages=4)
    (p,) = _prompts(cfg, [CACHE])
    with pytest.raises(RequestTooLarge):
        eng.submit(p, max_tokens=8)  # prompt + output exceeds cache_len
    with pytest.raises(RequestTooLarge):
        # fits the cache but its footprint exceeds the whole page pool
        eng.submit(p[:40], max_tokens=8)
    assert issubclass(RequestTooLarge, ValueError)  # legacy except clauses
    assert issubclass(RequestTooLarge, ServeError)
    assert eng.stats["overload_rejections"] == 0
    (ok,) = _prompts(cfg, [8], seed=1)
    assert eng.submit(ok, max_tokens=4).result()  # engine still serves


def test_submit_overload_raises_typed(qwen):
    cfg, params = qwen
    eng = _engine(params, cfg, max_queue=2)
    prompts = _prompts(cfg, [8, 8, 8])
    handles = [eng.submit(p, max_tokens=2) for p in prompts[:2]]
    with pytest.raises(EngineOverloaded):
        eng.submit(prompts[2], max_tokens=2)
    assert issubclass(EngineOverloaded, RuntimeError)
    assert eng.stats["overload_rejections"] == 1
    res = eng.run()
    assert all(len(res[h]) == 2 for h in handles)
    eng.submit(prompts[2], max_tokens=2).result()  # room again after drain


def test_deadline_expires_live_request_with_partial_tokens(qwen):
    cfg, params = qwen
    eng = _engine(params, cfg)
    (p,) = _prompts(cfg, [8])
    h = eng.submit(p, max_tokens=32, deadline_ticks=6)
    res = eng.run()
    assert eng.stats["deadline_expired"] == 1
    with pytest.raises(DeadlineExceeded) as exc:
        h.tokens_list = h.result()
    assert 1 <= len(exc.value.tokens) < 32  # partial output attached
    assert list(exc.value.tokens) == res.get(int(h), exc.value.tokens)
    assert issubclass(DeadlineExceeded, TimeoutError)
    assert _leak_free(eng)


def test_deadline_expires_starved_queued_request(qwen):
    cfg, params = qwen
    eng = _engine(params, cfg, max_pages=4, preempt=False)
    hog, chat = _prompts(cfg, [16, 6])
    eng.submit(hog, max_tokens=16)
    starved = eng.submit(chat, max_tokens=2, deadline_ticks=4)
    eng.run()
    with pytest.raises(DeadlineExceeded) as exc:
        starved.result()
    assert exc.value.tokens == []  # never admitted, nothing served
    assert _leak_free(eng)


def test_result_timeout_ticks_bounds_the_drain(qwen):
    cfg, params = qwen
    eng = _engine(params, cfg)
    (p,) = _prompts(cfg, [8])
    h = eng.submit(p, max_tokens=32)
    with pytest.raises(TimeoutError) as exc:
        h.result(timeout_ticks=2)
    assert not isinstance(exc.value, ServeError)  # a drain bound, not abort
    assert h.result() == list(h.request.out_tokens)  # finishes when drained


def test_engine_cancel_error_is_typed_cancelled(qwen):
    cfg, params = qwen
    eng = _engine(params, cfg)
    (p,) = _prompts(cfg, [8])
    h = eng.submit(p, max_tokens=32)
    for _ in range(3):
        eng.tick()
    eng.cancel(h, error=Cancelled("admin abort", tokens=None))
    with pytest.raises(Cancelled) as exc:
        h.result()
    assert len(exc.value.tokens) >= 1  # partial output rides the exception
    # CLIENT cancel keeps the historical contract: partial result, no raise
    h2 = eng.submit(p, max_tokens=32)
    for _ in range(3):
        eng.tick()
    h2.cancel()
    assert isinstance(h2.result(), list)
    assert _leak_free(eng)


# ---------------------------------------------------------------------------
# preempt_order policy hook (hand-built views, no engine)


def _pview(reqs):
    return EngineView(queue=(), slot_requests=tuple(reqs),
                      slot_fill=tuple(0 for _ in reqs), budget=32,
                      chunk=16, page_size=8, match_len=lambda p: 0)


def _reqs(specs):
    return [Request(uid=u, prompt=np.arange(4), priority=pr)
            for u, pr in specs]


def test_default_preempt_order_low_priority_young_first():
    view = _pview(_reqs([(0, 1), (1, 0), (2, 0), (3, 2)]))
    assert list(Scheduler().preempt_order(view, [0, 1, 2, 3])) == [2, 1, 0, 3]


def test_slo_preempt_order_exempts_interactive():
    view = _pview(_reqs([(0, 1), (1, 0), (2, 0), (3, 2)]))
    for sched in (SloScheduler(), ClassThenFamilyScheduler()):
        order = list(sched.preempt_order(view, [0, 1, 2, 3]))
        assert order == [2, 1]  # batch only, youngest first


# ---------------------------------------------------------------------------
# Roofline: preemption swap bytes priced like promotion bytes


def test_mixed_bound_prices_swap_like_promotion():
    from repro.configs import get_config
    from repro.core.roofline import mixed_bound

    cfg = get_config("qwen2-1.5b")
    kw = dict(n_decode=8, n_prefill=64, context_len=1024, page_size=16)
    base = mixed_bound(cfg, **kw)
    assert base["swap_s"] == 0.0 and base["swapped_bytes"] == 0.0
    promo = mixed_bound(cfg, promoted_pages=4, **kw)
    swap = mixed_bound(cfg, swapped_pages=4, **kw)
    # identical per-page bytes, identical H2D link: same third roof
    assert swap["swapped_bytes"] == promo["promoted_bytes"] > 0
    assert swap["promotion_s"] == pytest.approx(promo["promotion_s"])
    assert swap["tick_s"] == promo["tick_s"]
    both = mixed_bound(cfg, promoted_pages=4, swapped_pages=4, **kw)
    assert both["promotion_s"] == pytest.approx(2 * promo["promotion_s"])
