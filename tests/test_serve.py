"""Serving-engine tests: ragged single-program token identity (vs the seed
reference engine and solo decode), exactly-one-compiled-program assertions,
decode-never-stalls-during-prefill, seeded sampling, FIFO ordering and slot
reuse under churn, EOS / max-token termination, page-pool hygiene and
overcommit, the refcounted prefix cache (warm-prefix identity, mid-page COW
divergence, LRU eviction, refcount no-leak), and the Pallas ragged
paged-decode path (including aliased shared pages)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.configs.base import Stage
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.reference import ReferenceEngine

KEY = jax.random.PRNGKey(0)
CACHE = 64


def _setup(arch):
    # float32 keeps greedy argmax stable across batching layouts
    cfg = get_config(arch, smoke=True).replace(dtype="float32")
    params = M.init_params(KEY, cfg)
    return cfg, params


@pytest.fixture(scope="module")
def qwen():
    return _setup("qwen2-1.5b")


@pytest.fixture(scope="module")
def gemma():
    return _setup("gemma3-4b")  # 5:1 local(window=16):global mix


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, L) for L in lens]


def _solo_decode(params, cfg, prompt, max_tokens, cache_len=CACHE):
    """Batch-1 ground truth replicating the engines' decode scheme (full
    prompt prefill, then decode restarts from the last prompt token)."""
    state = M.init_decode_state(params, cfg, 1, cache_len)
    state = M.prefill(params, cfg, state, np.asarray(prompt, np.int32)[None])
    t = jnp.asarray([[int(prompt[-1])]], jnp.int32)
    out = []
    for _ in range(max_tokens):
        logits, state = M.decode_step(params, cfg, state, t)
        tok = int(jnp.argmax(logits[:, -1], -1)[0])
        out.append(tok)
        t = jnp.asarray([[tok]], jnp.int32)
    return out


def _serve(cfg, params, prompts, max_tokens=4, eos_id=None, **kw):
    kw.setdefault("batch_size", 3)
    kw.setdefault("cache_len", CACHE)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 16)
    eng = ServeEngine(params, cfg, **kw)
    uids = [eng.submit(p, max_tokens=max_tokens, eos_id=eos_id)
            for p in prompts]
    return eng, uids, eng.run()


# ---------------------------------------------------------------------------
# Token identity


def test_equal_length_wave_matches_reference_engine(qwen):
    """Greedy output bit-matches the seed engine on a single equal-length
    wave — the only traffic the lock-step seed engine serves correctly
    (on slot reuse its shared ``pos`` keeps the previous wave's maximum, so
    later waves decode at wrong positions; the paged engine instead matches
    the solo ground truth — see the churn tests)."""
    cfg, params = qwen
    prompts = _prompts(cfg, [12] * 3)
    ref = ReferenceEngine(params, cfg, batch_size=3, cache_len=CACHE)
    ref_uids = [ref.submit(p, max_tokens=5) for p in prompts]
    want = ref.run()
    _, uids, got = _serve(cfg, params, prompts, max_tokens=5)
    for ru, u in zip(ref_uids, uids):
        assert got[u] == want[ru]


def test_mixed_lengths_match_solo_runs(qwen):
    cfg, params = qwen
    lens = [5, 19, 11, 26]
    prompts = _prompts(cfg, lens, seed=1)
    _, uids, got = _serve(cfg, params, prompts, batch_size=2)
    for u, p in zip(uids, prompts):
        assert got[u] == _solo_decode(params, cfg, p, 4)


def test_windowed_layers_mixed_lengths(gemma):
    """Prompts longer than the sliding window exercise the per-slot
    circular buffers (chunk > window wraps within one scatter)."""
    cfg, params = gemma
    prompts = _prompts(cfg, [33, 7, 21], seed=2)
    _, uids, got = _serve(cfg, params, prompts, batch_size=2,
                          prefill_chunk=24)
    for u, p in zip(uids, prompts):
        assert got[u] == _solo_decode(params, cfg, p, 4)


def test_paged_matches_contiguous_cache(qwen):
    """page_size == cache_len is a contiguous cache (one page per slot);
    fine paging must produce identical tokens."""
    cfg, params = qwen
    prompts = _prompts(cfg, [5, 19, 11, 26], seed=3)
    _, u1, r1 = _serve(cfg, params, prompts, page_size=CACHE)
    _, u2, r2 = _serve(cfg, params, prompts, page_size=4)
    assert [r1[u] for u in u1] == [r2[u] for u in u2]


def test_chunked_prefill_matches_unchunked(qwen):
    """Splitting prompts into small chunks interleaved across ticks must
    not change the cache contents (greedy-token identity)."""
    cfg, params = qwen
    prompts = _prompts(cfg, [26, 9, 17], seed=4)
    _, u1, r1 = _serve(cfg, params, prompts, prefill_chunk=CACHE)
    _, u2, r2 = _serve(cfg, params, prompts, prefill_chunk=4)
    assert [r1[u] for u in u1] == [r2[u] for u in u2]


def test_flash_paged_decode_matches_jnp_path(qwen):
    cfg, params = qwen
    prompts = _prompts(cfg, [5, 19, 11], seed=5)
    _, u1, r1 = _serve(cfg, params, prompts, batch_size=2)
    _, u2, r2 = _serve(cfg, params, prompts, batch_size=2, flash_decode=True)
    assert [r1[u] for u in u1] == [r2[u] for u in u2]


def test_recurrent_hybrid_serves_correctly():
    """Masked recurrent rolls: per-slot states must not advance on pad
    tails or idle ticks (xlstm has no attention cache at all)."""
    cfg, params = _setup("xlstm-350m")
    prompts = _prompts(cfg, [5, 14, 9], seed=6)
    _, uids, got = _serve(cfg, params, prompts, batch_size=2)
    for u, p in zip(uids, prompts):
        assert got[u] == _solo_decode(params, cfg, p, 4)


# ---------------------------------------------------------------------------
# Ragged single program


def _reference_solo(params, cfg, prompt, max_tokens):
    """Ground truth via the seed ReferenceEngine at batch 1 — the only
    traffic shape it serves correctly for arbitrary lengths (its positions
    are lock-step across slots)."""
    ref = ReferenceEngine(params, cfg, batch_size=1, cache_len=CACHE)
    uid = ref.submit(prompt, max_tokens=max_tokens)
    return ref.run()[uid]


def test_ragged_mixed_concurrent_matches_reference(qwen):
    """Token identity on mixed-length concurrent traffic: every request out
    of the ragged pack matches the seed reference engine run solo."""
    cfg, params = qwen
    prompts = _prompts(cfg, [5, 19, 11, 26, 8], seed=21)
    eng, uids, got = _serve(cfg, params, prompts, batch_size=2,
                            token_budget=24)
    for u, p in zip(uids, prompts):
        assert got[u] == _reference_solo(params, cfg, p, 4)
    assert eng.stats["traces"] == 1


def test_exactly_one_program_for_any_traffic_mix(qwen):
    """The tentpole claim: one compiled program serves pure prefill, pure
    decode, and every blend — asserted by the trace counter AND the jit
    cache across two full runs with different traffic."""
    cfg, params = qwen
    eng = ServeEngine(params, cfg, batch_size=3, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=32)
    uids = [eng.submit(p, max_tokens=5)
            for p in _prompts(cfg, [26, 4, 17, 9, 12], seed=22)]
    got = eng.run()
    assert sorted(got) == sorted(uids)
    # second run, different mix, same engine: still the one program
    u2 = [eng.submit(p, max_tokens=2) for p in _prompts(cfg, [7, 7], seed=23)]
    got2 = eng.run()
    assert sorted(got2) == sorted(u2)
    assert eng.stats["traces"] == 1
    cache_size = getattr(eng._ragged_step, "_cache_size", lambda: 1)()
    assert cache_size == 1


@settings(max_examples=5, deadline=None)
@given(budget=st.sampled_from([8, 24, 64]),
       chunk=st.sampled_from([4, 8, 16]),
       page=st.sampled_from([4, 8, 64]))
def test_ragged_property_over_budget_chunk_page(qwen, budget, chunk, page):
    """Property: token identity and single-program compilation hold over
    random (token_budget, prefill_chunk, page_size) combos."""
    cfg, params = qwen
    prompts = _prompts(cfg, [5, 19, 11, 26], seed=1)
    eng, uids, got = _serve(cfg, params, prompts, batch_size=2,
                            prefill_chunk=chunk, page_size=page,
                            token_budget=budget)
    for u, p in zip(uids, prompts):
        assert got[u] == _solo_decode(params, cfg, p, 4)
    assert eng.stats["traces"] == 1


def test_decode_never_stalls_during_prefill(qwen):
    """The head-of-line fix: while a long document prefills, a decoding
    chat slot emits a token EVERY tick — decode tokens are packed first,
    the prefill chunk takes the leftover budget."""
    cfg, params = qwen
    [chat] = _prompts(cfg, [4], seed=30)
    [filler] = _prompts(cfg, [4], seed=31)
    [doc] = _prompts(cfg, [56], seed=32)

    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=8, token_budget=16)
    u_chat = eng.submit(chat, max_tokens=12)
    eng.submit(filler, max_tokens=1)  # frees its slot for the doc
    eng.submit(doc, max_tokens=2)  # admitted mid-chat-decode
    eng.run()
    ticks = [t for uid, t, _ in eng.token_log if uid == u_chat]
    assert max(np.diff(ticks)) == 1  # consecutive ticks, no stall
    # ...and the doc really was prefilling during several of those ticks
    assert sum(eng.tick_log[t][0] for t in ticks) >= 3


# ---------------------------------------------------------------------------
# Sampling


def test_seeded_sampling_deterministic_and_packing_invariant(qwen):
    """Seeded temperature/top-k sampling repeats exactly and is invariant
    to how ticks were packed (one RNG draw per emitted token)."""
    cfg, params = qwen
    prompts = _prompts(cfg, [8, 14], seed=40)

    def run(chunk, budget, temperature):
        eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                          page_size=8, prefill_chunk=chunk,
                          token_budget=budget)
        uids = [eng.submit(p, max_tokens=6, temperature=temperature,
                           top_k=50, seed=123 + i)
                for i, p in enumerate(prompts)]
        got = eng.run()
        return [got[u] for u in uids]

    a = run(16, 24, 8.0)
    assert a == run(16, 24, 8.0)  # same seeds -> same tokens
    assert a == run(8, 40, 8.0)  # packing-invariant
    assert a != run(16, 24, 0.0)  # actually samples (high temperature)


def test_top_k_one_is_greedy(qwen):
    cfg, params = qwen
    [prompt] = _prompts(cfg, [10], seed=41)
    eng = ServeEngine(params, cfg, batch_size=1, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=8)
    u1 = eng.submit(prompt, max_tokens=4, temperature=5.0, top_k=1, seed=0)
    u2 = eng.submit(prompt, max_tokens=4)  # greedy default
    got = eng.run()
    assert got[u1] == got[u2]


# a seeded draw at temperature 8 (top-k 50) beside a greedy request, as
# the engine served it when it still copied every logits row to the host
SAMPLED_TRANSCRIPT = [147, 100, 100, 362, 362, 362, 335]
GREEDY_TRANSCRIPT = [308] * 5


class _HostArgmax(ServeEngine):
    """Copies each sampled row through its handle and keeps the host's
    argmax of it beside the token the engine booked."""

    def _sample(self, req, row, ordinal):
        tok = super()._sample(req, row, ordinal)
        host = np.asarray(row)
        assert host.shape == row.shape == (self.cfg.vocab_size,)
        self.pairs.append((int(np.argmax(host)), tok, host.dtype))
        return tok


@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "speculative"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_greedy_tokens_equal_host_argmax(kv_dtype, spec_k):
    """The step's device argmax is the first maximum of the same logits
    the host would read, for every row sampled, verify rows included, at
    the activation dtype the benchmark's configurations serve in.  The
    tied embedding's second half copies its first, so every logit has an
    equal twin and only the first maximum matches."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = M.init_params(KEY, cfg)
    emb = params["embed"]["tok_embed"]
    half = cfg.vocab_size // 2
    params["embed"]["tok_embed"] = emb.at[half:].set(emb[:half])
    eng = _HostArgmax(params, cfg, batch_size=3, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=24,
                      kv_dtype=kv_dtype, spec_k=spec_k)
    eng.pairs = []
    rng = np.random.RandomState(9)
    for L in (5, 11, 20):
        eng.submit(np.tile(rng.randint(0, cfg.vocab_size, 4), L // 4 + 1)[:L],
                   max_tokens=8)
    eng.run()
    assert eng.pairs and all(h == t < half for h, t, _ in eng.pairs), \
        eng.pairs
    assert {d for *_, d in eng.pairs} == {np.dtype(cfg.dtype)}
    assert eng.stats["host_logit_rows"] == len(eng.pairs)
    if spec_k:
        assert eng.stats["spec_accepted"] > 0


def test_greedy_run_copies_no_logits_row(qwen):
    cfg, params = qwen
    eng, uids, got = _serve(cfg, params, _prompts(cfg, [5, 30, 17], seed=3),
                            max_tokens=6)
    assert all(len(got[u]) == 6 for u in uids)
    assert eng.stats["sampled_slot_ticks"] > 0
    assert eng.stats["host_logit_rows"] == 0


@pytest.mark.parametrize("spec_k", [0, 2], ids=["plain", "speculative"])
def test_sampled_rows_alone_copy_their_logits(qwen, spec_k):
    """A request drawn at a temperature copies one logits row per token it
    draws, its greedy neighbour none, and both transcripts are the ones
    the engine gave when it copied every row."""
    cfg, params = qwen
    sampled, greedy = _prompts(cfg, [9, 13], seed=42)
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=24,
                      spec_k=spec_k)
    u0 = eng.submit(sampled, max_tokens=7, temperature=8.0, top_k=50,
                    seed=2024)
    u1 = eng.submit(greedy, max_tokens=5)
    got = eng.run()
    assert got[u0] == SAMPLED_TRANSCRIPT
    assert got[u1] == GREEDY_TRANSCRIPT
    assert eng.stats["host_logit_rows"] == len(SAMPLED_TRANSCRIPT)


def test_sampling_validation(qwen):
    cfg, params = qwen
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=32, page_size=8)
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], temperature=-0.5)
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], temperature=1.0, top_k=0)
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, batch_size=4, token_budget=2)


# ---------------------------------------------------------------------------
# Scheduling / lifecycle


def test_fifo_ordering_and_slot_reuse_under_churn(qwen):
    """9 equal requests through 3 slots: three full waves, FIFO admission,
    every slot reused, pages recycled."""
    cfg, params = qwen
    prompts = _prompts(cfg, [8] * 9)
    eng, uids, got = _serve(cfg, params, prompts, max_tokens=3)
    assert sorted(got) == sorted(uids) and all(len(v) == 3 for v in got.values())
    waves = [set(eng.completion_order[i:i + 3]) for i in (0, 3, 6)]
    assert waves == [set(uids[0:3]), set(uids[3:6]), set(uids[6:9])]
    # completed prompt pages may stay resident as prefix cache (refcount 0,
    # evictable); every page must be reclaimable and unpinned
    assert not any(eng.slots) and (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages


def test_eos_termination(qwen):
    cfg, params = qwen
    [prompt] = _prompts(cfg, [10], seed=7)
    _, [u], free_run = _serve(cfg, params, [prompt], max_tokens=6)
    first = free_run[u][0]
    _, [u2], stopped = _serve(cfg, params, [prompt], max_tokens=6,
                              eos_id=first)
    assert stopped[u2] == [first]


def test_max_tokens_termination(qwen):
    cfg, params = qwen
    prompts = _prompts(cfg, [6, 6], seed=8)
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16)
    uids = [eng.submit(p, max_tokens=m) for p, m in zip(prompts, (2, 7))]
    got = eng.run()
    assert [len(got[u]) for u in uids] == [2, 7]


def test_page_pool_overcommit_queues_fifo(qwen):
    """batch_size=4 slots over a pool that only fits ~2 requests: admission
    waits for pages, everyone still completes with identical tokens."""
    cfg, params = qwen
    prompts = _prompts(cfg, [20, 24, 18, 22], seed=9)
    _, u_full, r_full = _serve(cfg, params, prompts, batch_size=4)
    pages_two = 2 * ((24 + 4 + 7) // 8)  # fits two largest requests
    eng, u_tight, r_tight = _serve(cfg, params, prompts, batch_size=4,
                                   max_pages=pages_two)
    assert [r_tight[u] for u in u_tight] == [r_full[u] for u in u_full]
    assert eng.stats["pages_in_use_peak"] <= pages_two
    assert (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages


def test_page_pool_returns_to_initial_after_three_waves(qwen):
    """Page-pool hygiene regression, extended to refcounts: admit/retire 3
    waves of requests through one engine and assert after every wave that
    every page has returned to refcount 0 and the pool is fully reclaimable
    (free + refcount-0 cached == n_pages — no leak, no pinned stragglers),
    including a wave terminated early by EOS."""
    cfg, params = qwen
    eng = ServeEngine(params, cfg, batch_size=3, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=32)
    n0 = len(eng._free)
    assert n0 == eng.n_pages
    eos = None
    for wave in range(3):
        prompts = _prompts(cfg, [9, 17, 12], seed=50 + wave)
        uids = [eng.submit(p, max_tokens=3, eos_id=eos) for p in prompts]
        got = eng.run()
        assert sorted(got) == sorted(uids)
        assert not any(eng.slots)
        assert (eng._ref == 0).all()
        assert eng.reclaimable_pages == eng.n_pages
        assert len(eng._free) + eng.cached_pages == eng.n_pages
        # next wave terminates via EOS on a token the model actually emits
        eos = got[uids[0]][0]
    # wave 4: cancellations mid-flight — one admitted request cancelled
    # after its first tick, one cancelled while still queued; hygiene must
    # hold exactly as for completed waves
    prompts = _prompts(cfg, [9, 17, 12], seed=53)
    handles = [eng.submit(p, max_tokens=4) for p in prompts]
    extra = eng.submit(prompts[0], max_tokens=4)  # queued: 3 slots taken
    eng.tick()
    assert handles[1].cancel() and extra.cancel()
    got = eng.run()
    assert sorted(got) == sorted([handles[0], handles[2]])
    assert not any(eng.slots)
    assert (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages
    # dropping the cache returns every page to the free list
    eng.drop_prefix_cache()
    assert len(eng._free) == eng.n_pages and eng.cached_pages == 0


def test_reset_template_holds_no_pool_copy(qwen):
    """The slot-reset template keeps every per-slot leaf of the decode
    state but no copy of the shared KV pools (which the reset never reads
    from it): on the chip a second pool would not fit beside the first."""
    cfg, params = qwen
    eng, uids, got = _serve(cfg, params, _prompts(cfg, [9, 17, 12, 5]))
    assert sorted(got) == sorted(uids)  # 4 requests on 3 slots: a reuse
    for st_blks, t_blks in zip(eng._state["layers"], eng._template["layers"]):
        for s_blk, t_blk in zip(st_blks, t_blks):
            pools = {"kp", "vp", "ks", "vs"} & set(s_blk)
            assert pools and not pools & set(t_blk)
            assert set(t_blk) == set(s_blk) - pools


def test_submit_validation(qwen):
    cfg, params = qwen
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=32, page_size=8)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(30, np.int32), max_tokens=8)  # > cache_len
    with pytest.raises(ValueError):
        eng.submit([], max_tokens=4)  # empty prompt
    eng2 = ServeEngine(params, cfg, batch_size=2, cache_len=64, page_size=8,
                       max_pages=2)
    with pytest.raises(ValueError):
        eng2.submit(np.zeros(40, np.int32), max_tokens=8)  # > whole pool


def test_tick_budget_exhaustion_releases_slots(qwen):
    """A run() cut off mid-decode returns partials, frees every page, and
    leaves the engine reusable (fresh run produces correct tokens)."""
    cfg, params = qwen
    prompts = _prompts(cfg, [9, 9], seed=10)
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16)
    uids = [eng.submit(p, max_tokens=6) for p in prompts]
    # 3 ragged ticks: the first packs the whole 9-token prompt AND the
    # first decode token, so each request has 3 of its 6 tokens
    partial = eng.run(max_ticks=3)
    assert all(len(partial[u]) == 3 for u in uids)
    assert eng.reclaimable_pages == eng.n_pages and not any(eng.slots)
    assert (eng._ref == 0).all()
    # resubmitting hits the prefix cached by the truncated run and must
    # still be token-identical to the solo ground truth
    u2 = eng.submit(prompts[0], max_tokens=4)
    assert eng.run()[u2] == _solo_decode(params, cfg, prompts[0], 4)
    assert eng.stats["prefix_hits"] >= 1


# ---------------------------------------------------------------------------
# Prefix cache: warm hits, COW divergence, eviction, refcount hygiene


def _with_prefix(shared, suffixes):
    return [np.concatenate([shared, s]) for s in suffixes]


def test_warm_prefix_hits_are_token_identical(qwen):
    """A second wave reusing a cached system prompt skips its prefill
    (prefix_hits / prefix_tokens_reused advance, packed tokens drop) and
    stays bit-identical to the solo ground truth — with the serve path
    still exactly one compiled program."""
    cfg, params = qwen
    [shared] = _prompts(cfg, [40], seed=60)  # 5 full pages at page_size=8
    wave1 = _with_prefix(shared, _prompts(cfg, [5, 7], seed=61))
    wave2 = _with_prefix(shared, _prompts(cfg, [6, 4], seed=62))
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=32)
    u1 = [eng.submit(p, max_tokens=4) for p in wave1]
    r1 = eng.run()
    cold_packed = eng.stats["packed_tokens"]
    u2 = [eng.submit(p, max_tokens=4) for p in wave2]
    r2 = eng.run()
    warm_packed = eng.stats["packed_tokens"] - cold_packed
    for u, p in zip(u1 + u2, wave1 + wave2):
        assert {**r1, **r2}[u] == _solo_decode(params, cfg, p, 4)
    assert eng.stats["prefix_hits"] >= 2  # both wave-2 requests hit
    assert eng.stats["prefix_tokens_reused"] >= 2 * 40
    assert warm_packed < cold_packed / 2  # the prefill compute was skipped
    assert eng.stats["traces"] == 1
    assert (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages


def test_fully_cached_prompt_skips_straight_to_decode(qwen):
    """A prompt that is one exact full cached page starts decoding on its
    first tick (zero prefill tokens packed for it)."""
    cfg, params = qwen
    [p] = _prompts(cfg, [16], seed=63)  # exactly 2 pages at page_size=8
    eng = ServeEngine(params, cfg, batch_size=1, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=8)
    u1 = eng.submit(p, max_tokens=3)
    r1 = eng.run()
    packed_cold = eng.stats["packed_tokens"]
    u2 = eng.submit(p, max_tokens=3)
    r2 = eng.run()
    assert r2[u2] == r1[u1] == _solo_decode(params, cfg, p, 3)
    # warm run packs exactly one decode token per emitted token
    assert eng.stats["packed_tokens"] - packed_cold == 3
    assert eng.stats["prefix_tokens_reused"] == 16


def test_cow_divergence_mid_page(qwen):
    """Two prompts sharing 18 tokens then diverging mid-page (page_size=8):
    the second rides 2 shared pages plus a COW copy of the third (a full
    cached page whose tail it overwrites), and both outputs match their solo
    ground truths."""
    cfg, params = qwen
    rng = np.random.RandomState(70)
    a = rng.randint(0, cfg.vocab_size, 26)  # 3 FULL pages + a partial tail
    b = a.copy()
    b[18:] = (b[18:] + 1) % cfg.vocab_size
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=32)
    ua = eng.submit(a, max_tokens=4)
    ra = eng.run()
    ub = eng.submit(b, max_tokens=4)
    rb = eng.run()
    assert ra[ua] == _solo_decode(params, cfg, a, 4)
    assert rb[ub] == _solo_decode(params, cfg, b, 4)
    assert eng.stats["cow_copies"] == 1
    assert eng.stats["prefix_tokens_reused"] == 18  # 2 full pages + 2 in-page
    assert (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages


@settings(max_examples=6, deadline=None)
@given(share=st.sampled_from([3, 9, 16, 21, 27]),
       page=st.sampled_from([4, 8]))
def test_cow_property_shared_then_divergent(qwen, share, page):
    """Property: for any shared-prefix length (page-aligned or mid-page) and
    page size, serving A then a B that diverges at ``share`` reuses exactly
    the shared tokens covered by A's FULL (indexable) pages — a mid-page
    share point costs one COW copy — and both stay token-identical to solo
    decode, with the pool fully reclaimable after."""
    cfg, params = qwen
    rng = np.random.RandomState(71)
    a = rng.randint(0, cfg.vocab_size, 28)
    b = a.copy()
    b[share:] = (b[share:] + 1 + rng.randint(0, 100)) % cfg.vocab_size
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=page, prefill_chunk=8, token_budget=16)
    ua = eng.submit(a, max_tokens=4)
    ra = eng.run()
    ub = eng.submit(b, max_tokens=4)
    rb = eng.run()
    assert ra[ua] == _solo_decode(params, cfg, a, 4)
    assert rb[ub] == _solo_decode(params, cfg, b, 4)
    # only A's full pages enter the index: its partial tail page is private
    reusable = min(share, (len(a) // page) * page)
    assert eng.stats["prefix_tokens_reused"] == reusable
    assert eng.stats["cow_copies"] == (1 if reusable % page else 0)
    assert (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages


def test_prefix_cache_lru_eviction_under_pressure(qwen):
    """A pool sized for ~2 requests serving many distinct prompts must evict
    cached pages (LRU over refcount-0) instead of deadlocking, and still
    produce correct tokens; a recently cached prefix still hits."""
    cfg, params = qwen
    prompts = _prompts(cfg, [20, 24, 18, 22, 21, 19], seed=72)
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=32,
                      max_pages=8)
    uids = [eng.submit(p, max_tokens=3) for p in prompts]
    got = eng.run()
    for u, p in zip(uids, prompts):
        assert got[u] == _solo_decode(params, cfg, p, 3)
    assert eng.stats["evictions"] > 0
    assert (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages
    # the LAST prompt's pages are the freshest cache entries: resubmitting
    # it hits even in the tight pool
    hits0 = eng.stats["prefix_hits"]
    u2 = eng.submit(prompts[-1], max_tokens=3)
    assert eng.run()[u2] == got[uids[-1]]
    assert eng.stats["prefix_hits"] > hits0


def test_prefix_sharing_concurrent_in_flight(qwen):
    """A request admitted while the prefix OWNER is still decoding shares
    the owner's pages (refcount > 1 on the shared pages, asserted
    mid-flight via the tick API) — and both finish correctly."""
    cfg, params = qwen
    [shared] = _prompts(cfg, [24], seed=73)
    a, b = _with_prefix(shared, _prompts(cfg, [4, 6], seed=74))
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=32)
    ua = eng.submit(a, max_tokens=12)
    done = {}
    for _ in range(3):  # a finishes prefill and starts decoding
        done.update(eng.tick())
    ub = eng.submit(b, max_tokens=2)
    done.update(eng.tick())  # b admitted while a decodes
    assert eng.stats["prefix_hits"] == 1
    shared_pages = 24 // 8
    assert (eng._ref == 2).sum() == shared_pages  # pages aliased by a and b
    while not eng.idle:
        done.update(eng.tick())
    assert done[ua] == _solo_decode(params, cfg, a, 12)
    assert done[ub] == _solo_decode(params, cfg, b, 2)
    assert (eng._ref == 0).all()


def test_flash_ragged_shared_pages_match_jnp(qwen):
    """The Pallas ragged kernel needs no change for aliased block-table
    rows: warm-prefix traffic through flash_decode=True matches the jnp
    path token for token, with hits on both engines."""
    cfg, params = qwen
    [shared] = _prompts(cfg, [32], seed=75)
    prompts = _with_prefix(shared, _prompts(cfg, [5, 3], seed=76))

    def run(flash):
        eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                          page_size=8, prefill_chunk=16, token_budget=32,
                          flash_decode=flash)
        u1 = [eng.submit(p, max_tokens=4) for p in prompts]
        r1 = eng.run()
        u2 = [eng.submit(p, max_tokens=4) for p in prompts]
        r2 = eng.run()
        assert eng.stats["prefix_hits"] >= 2
        return [r1[u] for u in u1] + [r2[u] for u in u2]

    assert run(False) == run(True)


def _unrolled(cfg, params):
    """The same model with each scanned stage laid out as its layers, one
    block each (a stage of repeats 1, no layer scan): every layer keeps its
    own pools."""
    stages, stage_params = [], []
    for st, sp in zip(cfg.stages, params["stages"]):
        stages.append(Stage(st.pattern * st.repeats, 1) if st.repeats > 1
                      else st)
        stage_params.append(
            [jax.tree.map(lambda x, r=r: x[r], blk)
             for r in range(st.repeats) for blk in sp]
            if st.repeats > 1 else sp)
    return cfg.replace(stages=tuple(stages)), {**params,
                                               "stages": stage_params}


@pytest.mark.parametrize("arch,flash", [
    ("qwen2-1.5b", False), ("qwen2-1.5b", True),
    ("jamba-1.5-large-398b", False),
], ids=["qwen-jnp", "qwen-flash", "jamba-hybrid"])
def test_scanned_stage_matches_unrolled_layers(arch, flash):
    """A scanned stage carries its KV pools stacked through the layer loop
    and each layer writes and reads them at its own index; the same layers
    unrolled, each with its own pools, serve token-identical greedy
    transcripts and hold the same state, leaf by leaf and layer by layer.
    Attention-only (with a warm prefix hit and a copy-on-write on both
    engines, and through the kernel) and a hybrid whose mamba state is
    scanned as before beside the carried pools."""
    cfg, params = _setup(arch)
    assert cfg.stages[0].repeats > 1
    rng = np.random.RandomState(80)
    a = rng.randint(0, cfg.vocab_size, 26)  # 3 full pages + a partial tail
    b = a.copy()
    b[18:] = (b[18:] + 1) % cfg.vocab_size  # diverges mid-page: a COW
    c = rng.randint(0, cfg.vocab_size, 9)

    def run(cfg, params):
        eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                          page_size=8, prefill_chunk=16, token_budget=32,
                          flash_decode=flash)
        outs = []
        for wave in ([a, c], [b, a]):
            uids = [eng.submit(p, max_tokens=4) for p in wave]
            got = eng.run()
            outs += [got[u] for u in uids]
        return outs, eng

    scanned, eng = run(cfg, params)
    unrolled, flat = run(*_unrolled(cfg, params))
    assert scanned == unrolled
    # greedy tokens of an untrained model hardly read the context; the
    # pools show a layer that wrote to or read from another's plane
    (blocks,), (flat_blocks,) = eng._state["layers"], flat._state["layers"]
    for i, blk in enumerate(blocks):
        for r in range(cfg.stages[0].repeats):
            twin = flat_blocks[r * len(blocks) + i]
            assert set(twin) == set(blk)
            for name, leaf in blk.items():
                np.testing.assert_array_equal(np.asarray(leaf[r]),
                                              np.asarray(twin[name]))
    if arch == "qwen2-1.5b":
        for e in (eng, flat):
            assert e.stats["prefix_hits"] >= 2, e.stats
            assert e.stats["cow_copies"] >= 1, e.stats


def test_prefix_cache_disabled_for_hybrid_models(gemma):
    """Windowed circular buffers and recurrent states are per-slot and
    cannot be inherited from shared pages: sharing is auto-disabled (and
    explicit opt-out works on shareable models too)."""
    cfg_g, params_g = gemma
    eng = ServeEngine(params_g, cfg_g, batch_size=2, cache_len=CACHE,
                      page_size=8)
    assert not eng.prefix_cache
    cfg_x = get_config("xlstm-350m", smoke=True)
    params_x = M.init_params(KEY, cfg_x)
    eng = ServeEngine(params_x, cfg_x, batch_size=2, cache_len=CACHE,
                      page_size=8)
    assert not eng.prefix_cache


def test_prefix_cache_opt_out_matches_opt_in(qwen):
    """prefix_cache=False serves identical warm traffic with zero hits and
    identical tokens (the A/B knob the benchmark sweeps)."""
    cfg, params = qwen
    [shared] = _prompts(cfg, [24], seed=77)
    prompts = _with_prefix(shared, _prompts(cfg, [4, 5], seed=78))

    def run(on):
        eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                          page_size=8, prefill_chunk=16, token_budget=32,
                          prefix_cache=on)
        outs = []
        for _ in range(2):
            uids = [eng.submit(p, max_tokens=4) for p in prompts]
            got = eng.run()
            outs += [got[u] for u in uids]
        return outs, eng.stats

    on_outs, on_stats = run(True)
    off_outs, off_stats = run(False)
    assert on_outs == off_outs
    assert on_stats["prefix_hits"] >= 2 and off_stats["prefix_hits"] == 0
    assert off_stats["prefix_tokens_reused"] == 0


def test_tick_api_continuous_arrivals(qwen):
    """Requests submitted mid-flight through the public tick() API (the
    continuous-arrival driver contract) complete token-identically."""
    cfg, params = qwen
    prompts = _prompts(cfg, [9, 13, 7], seed=79)
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=32)
    uids = [eng.submit(prompts[0], max_tokens=5)]
    done = {}
    done.update(eng.tick())
    uids.append(eng.submit(prompts[1], max_tokens=5))
    done.update(eng.tick())
    uids.append(eng.submit(prompts[2], max_tokens=5))
    for _ in range(64):
        if eng.idle:
            break
        done.update(eng.tick())
    for u, p in zip(uids, prompts):
        assert done[u] == _solo_decode(params, cfg, p, 5)
    assert eng.stats["traces"] == 1


def test_admission_feasible_when_match_pins_all_evictable_pages(qwen):
    """Corner from review: the head request's own matched (refcount-0)
    pages and COW source must not be counted as evictable supply for its
    allocation.  Here the request's footprint equals the whole pool, every
    cached page belongs to its own match, and pinning the COW source too
    would leave the pool one page short — the engine must forgo the
    partial-page COW and admit on the full-page match alone rather than
    dying in _alloc or waiting forever."""
    cfg, params = qwen
    rng = np.random.RandomState(80)
    a = rng.randint(0, cfg.vocab_size, 24)  # 3 full pages cached after run
    eng = ServeEngine(params, cfg, batch_size=1, cache_len=56, page_size=8,
                      prefill_chunk=16, token_budget=16, max_pages=7)
    ua = eng.submit(a, max_tokens=8)
    ra = eng.run()
    assert eng.cached_pages == 3 and (eng._ref == 0).all()
    # b: 2 full pages + 3-token mid-page lcp of a, then diverges; its
    # 7-page footprint is the ENTIRE pool
    b = np.concatenate([a[:19], rng.randint(0, cfg.vocab_size, 25)])
    ub = eng.submit(b, max_tokens=8)
    rb = eng.run()
    assert rb[ub] == _solo_decode(params, cfg, b, 8)
    assert eng.stats["cow_copies"] == 0  # COW forgone, not crashed
    assert eng.stats["prefix_tokens_reused"] == 16  # full-page match kept
    assert (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages


@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "speculative"])
def test_kernel_visit_counters_match_the_helper(qwen, spec_k):
    """``attn_runs`` / ``attn_kv_tiles`` are the kernel helper's runs and
    KV tiles summed over the packs the engine dispatched; with
    speculation a slot's draft chain sits apart from its decode token, so
    some pack holds one slot in two runs."""
    from repro.kernels.flash_attention import ragged_visits

    cfg, params = qwen
    eng = ServeEngine(params, cfg, batch_size=3, cache_len=CACHE, page_size=8,
                      prefill_chunk=16, token_budget=48, flash_decode=True,
                      spec_k=spec_k)
    packs = []
    step = eng._ragged_step

    def recording(params, state, tokens, slot, q_pos, seq_idx, valid, li):
        packs.append((slot.copy(), np.where(valid, q_pos + 1, 0)))
        return step(params, state, tokens, slot, q_pos, seq_idx, valid, li)

    eng._ragged_step = recording
    rng = np.random.RandomState(5)
    prompts = ([np.tile(rng.randint(0, cfg.vocab_size, 4), 6)
                for _ in range(3)] if spec_k else _prompts(cfg, [5, 30, 17]))
    for p in prompts:
        eng.submit(p, max_tokens=6)
    eng.run()
    a = next(blk.attn for st in cfg.stages for blk in st.pattern
             if blk.mixer == "attn")
    shapes = dict(kvH=a.num_kv_heads, G=a.num_heads // a.num_kv_heads,
                  hd=a.head_dim, page=8, pps=eng.pps, kv_dtype=eng.kv_dtype)
    visits = [ragged_visits(s, n, **shapes) for s, n in packs]
    assert eng.stats["attn_runs"] == sum(r for r, _ in visits) > 0
    assert eng.stats["attn_kv_tiles"] == sum(t for _, t in visits) > 0
    if spec_k:
        assert eng.stats["spec_drafted"] > 0
        assert any(r > len(set(s[n > 0].tolist()))
                   for (s, n), (r, _) in zip(packs, visits))
