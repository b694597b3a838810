"""Speculative decoding inside the ragged token budget.

The contract under test, layer by layer:

- ``prompt_lookup_draft`` — the model-free drafter: longest tail n-gram,
  latest earlier occurrence, k-capped, [] on a miss.
- ``SpeculativeScheduler`` — a pure wrapper: every ordering delegates to
  the inner policy verbatim; only ``draft`` is new.
- The engine — drafts pack into the LEFTOVER (T,) budget after decode and
  prefill (strict priority: non-spec packing is bit-identical with spec
  on), one forward verifies every chain through a (B, 1+spec_k)
  ``logit_idx``, the longest agreeing prefix is accepted, and rejected
  tails roll kpos/slen back — all with ``stats["traces"] == 1``.
- Exactness — greedy transcripts are token-identical with speculation on
  or off; with per-(request, position) seeded sampling the same holds at
  ANY temperature, and sampling is packing-invariant even without
  speculation (the satellite regression).
- The analytic side — ``mixed_bound(draft_tokens=, accept_rate=)`` prices
  verify tokens as compute + KV writes but zero extra KV reads, and the
  tuner's ``spec_ks`` axis scores accepted-token goodput.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import (SloScheduler, SpeculativeScheduler,
                                   make_scheduler, prompt_lookup_draft)

KEY = jax.random.PRNGKey(0)
CACHE = 128


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-1.5b", smoke=True).replace(dtype="float32")
    params = M.init_params(KEY, cfg)
    return cfg, params


def _solo_decode(params, cfg, prompt, max_tokens, cache_len=CACHE):
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


def _engine(params, cfg, **kw):
    kw.setdefault("batch_size", 3)
    kw.setdefault("cache_len", CACHE)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("token_budget", 48)
    return ServeEngine(params, cfg, **kw)


def _tiled_prompts(cfg, n, pattern_len=6, reps=6, seed=7):
    """Repetitive completion prompts: a short pattern tiled — the greedy
    continuation loops, which prompt lookup predicts almost perfectly."""
    rng = np.random.RandomState(seed)
    return [np.tile(rng.randint(0, cfg.vocab_size, pattern_len), reps)
            for _ in range(n)]


def _small_alphabet_prompts(cfg, n, seed=11):
    """Prompts over a tiny token alphabet: lookup always finds a repeated
    n-gram but the model's actual continuation disagrees often — the
    reject/rollback workload."""
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 5, 40) for _ in range(n)]


# ---------------------------------------------------------------------------
# Drafter + wrapper units


def test_prompt_lookup_draft_finds_latest_continuation():
    # tail [2,3] recurs at index 1; its continuation follows
    assert prompt_lookup_draft([1, 2, 3, 4, 2, 3], 3) == [4, 2, 3]
    assert prompt_lookup_draft([1, 2, 3, 4, 2, 3], 1) == [4]
    # among equal-length matches the LATEST earlier occurrence wins
    assert prompt_lookup_draft([5, 1, 2, 6, 1, 2, 7, 1, 2], 1) == [7]
    # nothing repeats -> no draft, zero model work
    assert prompt_lookup_draft([1, 2, 3, 4, 5], 4) == []
    assert prompt_lookup_draft([1], 4) == []
    assert prompt_lookup_draft([1, 2, 1, 2], 0) == []
    # longer n-grams are preferred: [9,1,2] tail matches exactly once even
    # though the 1-gram [2] has a nearer (different-continuation) match
    assert prompt_lookup_draft([9, 1, 2, 8, 2, 5, 9, 1, 2], 1,
                               ngram_max=3) == [8]


def test_speculative_scheduler_delegates_orderings():
    inner = SloScheduler()
    s = SpeculativeScheduler(inner, spec_k=3)
    assert s.inner is inner
    assert s.name == "speculative(slo,k=3)"
    # orderings are the inner policy's, method for method

    class V:  # duck-typed view: delegation never inspects it
        queue = ()

    v = V()
    assert list(s.admission_order(v)) == list(inner.admission_order(v))
    assert s.decode_order(v, [2, 0, 1]) == inner.decode_order(v, [2, 0, 1])
    # draft is capped at spec_k even when asked for more: the tail 3-gram
    # [2,1,2] matches at index 1 and only 2 tokens follow it
    assert s.draft([1, 2, 1, 2, 1, 2], 99) == [1, 2]
    assert s.draft([7, 8, 9, 7, 8, 9, 7, 8, 9], 99) == [7, 8, 9]
    # registry resolution and default inner (FIFO)
    r = make_scheduler("speculative")
    assert isinstance(r, SpeculativeScheduler) and r.inner.name == "fifo"


def test_speculative_scheduler_validates():
    with pytest.raises(ValueError):
        SpeculativeScheduler(spec_k=0)
    with pytest.raises(ValueError):
        SpeculativeScheduler(spec_k=2, ngram_min=0)
    with pytest.raises(ValueError):
        SpeculativeScheduler(spec_k=2, ngram_min=3, ngram_max=2)


def test_engine_validates_spec_k(qwen):
    cfg, params = qwen
    with pytest.raises(ValueError):
        _engine(params, cfg, spec_k=-1)


# ---------------------------------------------------------------------------
# The tentpole: accept >1 token per slot-tick, stay exact, one trace


def test_spec_greedy_identical_fewer_ticks_one_trace(qwen):
    cfg, params = qwen
    prompts = _tiled_prompts(cfg, 3)
    runs = {}
    for k in (0, 4):
        eng = _engine(params, cfg, spec_k=k)
        uids = [eng.submit(p, max_tokens=24) for p in prompts]
        got = eng.run()
        runs[k] = ([got[u] for u in uids], dict(eng.stats), eng)
    off, on = runs[0], runs[4]
    # exactness: verification accepts only what greedy would have emitted
    assert on[0] == off[0]
    assert off[0][0] == _solo_decode(params, cfg, prompts[0], 24)
    # speculation is a packing policy, not a new program
    assert on[1]["traces"] == off[1]["traces"] == 1
    # the point: >1 accepted token per sampled slot-tick, fewer ticks
    assert on[1]["spec_accepted"] > 0
    per_tick = sum(len(t) for t in on[0]) / on[1]["sampled_slot_ticks"]
    assert per_tick > 1.0, on[1]
    assert on[1]["ticks"] < off[1]["ticks"]
    # the ledger balances: every drafted token was accepted or rejected
    assert (on[1]["spec_drafted"]
            == on[1]["spec_accepted"] + on[1]["spec_rejected"])
    # both pools drain clean
    for _, _, eng in runs.values():
        assert eng.reclaimable_pages == eng.n_pages


def _assert_no_stale_rows(eng):
    """After any tick, no slot may have KV metadata at positions it has not
    reached: a rejected draft tail that skipped rollback would leave
    kpos >= pos rows that poison the jnp-path mask on later ticks."""
    leaves = jax.tree_util.tree_flatten_with_path(eng._state)[0]
    for b, s in enumerate(eng.slots):
        if s is None:
            continue
        # mid-prefill s.pos is still 0 and ``fill`` tracks written rows;
        # once decoding, pos is the next position and fill the prompt len
        lim = max(s.pos, s.fill)
        for path, leaf in leaves:
            name = [p.key for p in path
                    if isinstance(p, jax.tree_util.DictKey)][-1]
            if name == "kpos":
                assert int(np.asarray(leaf)[..., b, :].max()) < lim, (
                    b, lim, np.asarray(leaf)[..., b, :].max())
            elif name == "slen":
                assert int(np.asarray(leaf)[..., b].max()) <= lim


def test_spec_rejection_rolls_back_kpos_slen(qwen):
    cfg, params = qwen
    prompts = _small_alphabet_prompts(cfg, 3)
    eng = _engine(params, cfg, spec_k=4)
    handles = [eng.submit(p, max_tokens=16) for p in prompts]
    while not eng.idle:
        eng.tick()
        _assert_no_stale_rows(eng)
    # the workload actually exercised the reject path
    assert eng.stats["spec_rejected"] > 0
    assert eng.stats["spec_rollbacks"] > 0
    # ...and the transcripts still match non-speculative exactly
    ref = _engine(params, cfg, spec_k=0)
    ruids = [ref.submit(p, max_tokens=16) for p in prompts]
    rgot = ref.run()
    assert [h.result() for h in handles] == [rgot[u] for u in ruids]
    assert eng.reclaimable_pages == eng.n_pages


def test_spec_identical_at_temperature_with_seed(qwen):
    """Per-(request, position) seeded sampling: the verify loop re-samples
    position j from draft-row logits, so identity must hold at any
    temperature — not just greedy argmax."""
    cfg, params = qwen
    prompts = _tiled_prompts(cfg, 2, seed=19)
    outs = {}
    for k in (0, 5):
        eng = _engine(params, cfg, spec_k=k)
        uids = [eng.submit(p, max_tokens=16, temperature=2.0, top_k=40,
                           seed=100 + i) for i, p in enumerate(prompts)]
        got = eng.run()
        outs[k] = [got[u] for u in uids]
    assert outs[0] == outs[5]


def test_seeded_sampling_is_packing_invariant_single_emit(qwen):
    """The satellite regression (no speculation anywhere): a seeded
    temperature request must produce the same tokens whether it runs solo
    or packed beside co-traffic — the RNG is keyed by (seed, position),
    not by a per-request draw sequence that co-traffic could shift."""
    cfg, params = qwen
    [p] = _tiled_prompts(cfg, 1, seed=23)
    solo = _engine(params, cfg, batch_size=1)
    u = solo.submit(p, max_tokens=12, temperature=1.5, top_k=16, seed=77)
    alone = solo.run()[u]
    busy = _engine(params, cfg, batch_size=3)
    rng = np.random.RandomState(29)
    co = [busy.submit(rng.randint(0, cfg.vocab_size, 30), max_tokens=20)
          for _ in range(2)]
    u2 = busy.submit(p, max_tokens=12, temperature=1.5, top_k=16, seed=77)
    assert busy.run()[u2] == alone
    assert co  # co-traffic actually shared the packs


# ---------------------------------------------------------------------------
# Edges: budget, max_tokens, gating, quantized pool


def test_spec_respects_max_tokens_mid_chain(qwen):
    """A draft chain may not run a request past max_tokens: the engine caps
    the packed room at max_tokens - emitted - 1, so the final emission
    still lands exactly on the cap with spec on."""
    cfg, params = qwen
    prompts = _tiled_prompts(cfg, 2, seed=31)
    outs = {}
    for k in (0, 6):
        eng = _engine(params, cfg, spec_k=k)
        uids = [eng.submit(p, max_tokens=5) for p in prompts]
        got = eng.run()
        outs[k] = [got[u] for u in uids]
        assert all(len(t) == 5 for t in outs[k])
    assert outs[0] == outs[6]


def test_spec_budget_tight_packs_no_drafts(qwen):
    """Zero leftover budget: decode-first strict priority means NO draft
    ever packs (a single slot whose decode token fills the whole budget)
    and the engine degrades to exactly the non-speculative tick.  With
    several slots, ramp-up ticks (others still prefilling) legitimately
    leave room — there the gate is output identity, not a draft-free pack."""
    cfg, params = qwen
    prompts = _tiled_prompts(cfg, 3, seed=37)
    [p] = prompts[:1]
    solo = {}
    for k in (0, 4):
        eng = _engine(params, cfg, batch_size=1, token_budget=1,
                      prefill_chunk=1, spec_k=k)
        u = eng.submit(p, max_tokens=8)
        solo[k] = eng.run()[u]
        if k:
            assert eng.stats["spec_drafted"] == 0
    assert solo[0] == solo[4]
    outs = {}
    for k in (0, 4):
        eng = _engine(params, cfg, batch_size=3, token_budget=3,
                      prefill_chunk=2, spec_k=k)
        uids = [eng.submit(q, max_tokens=8) for q in prompts]
        got = eng.run()
        outs[k] = [got[u] for u in uids]
    assert outs[0] == outs[4]


def test_spec_gated_off_for_hybrid_attention():
    """Windowed/hybrid models can't host drafts (rollback metadata only
    covers the paged global path), so spec_k silently gates to 0 — same
    convention as the prefix cache — and the engine still serves."""
    cfg = get_config("gemma3-4b", smoke=True).replace(dtype="float32")
    params = M.init_params(KEY, cfg)
    eng = _engine(params, cfg, spec_k=4)
    assert eng.stats["spec_k"] == 0
    rng = np.random.RandomState(41)
    u = eng.submit(rng.randint(0, cfg.vocab_size, 20), max_tokens=6)
    got = eng.run()
    assert len(got[u]) == 6
    assert eng.stats["spec_drafted"] == 0


def test_spec_identical_on_int8_pool(qwen):
    """Quantize-at-write + speculation: draft rows quantize exactly like
    decode rows, and rollback touches only metadata (never scale rows), so
    int8 transcripts stay identical across spec on/off."""
    cfg, params = qwen
    prompts = _tiled_prompts(cfg, 2, seed=43)
    outs = {}
    for k in (0, 4):
        eng = _engine(params, cfg, kv_dtype="int8", spec_k=k)
        uids = [eng.submit(p, max_tokens=16) for p in prompts]
        got = eng.run()
        outs[k] = [got[u] for u in uids]
        assert eng.reclaimable_pages == eng.n_pages
    assert outs[0] == outs[4]


# ---------------------------------------------------------------------------
# Analytic layer: roofline asymmetry + the tuner's spec axis


def test_mixed_bound_draft_terms():
    from repro.core.roofline import mixed_bound

    cfg = get_config("qwen2-1.5b")
    kw = dict(n_decode=8, n_prefill=0, context_len=512, page_size=16,
              kv_dtype="int8")
    base = mixed_bound(cfg, **kw)
    spec = mixed_bound(cfg, draft_tokens=4, accept_rate=0.7, **kw)
    # defaults are bit-identical (the axis is invisible until used)
    assert base == mixed_bound(cfg, draft_tokens=0.0, accept_rate=0.0, **kw)
    # the asymmetry that makes verification near-free on memory-bound
    # ticks: drafts add KV WRITES and compute but zero extra KV READS
    # (they ride the slot's existing page-stream)
    assert spec["kv_read_bytes"] == base["kv_read_bytes"]
    assert spec["kv_write_bytes"] > base["kv_write_bytes"]
    # goodput: tokens_per_s is EMITTED tokens, so acceptance scales it
    assert spec["tokens_per_s"] > base["tokens_per_s"]
    assert spec["accepted_per_slot_tick"] == pytest.approx(1 + 0.7 * 4)
    assert spec["drafted_tokens"] == pytest.approx(8 * 4)
    assert base["accepted_per_slot_tick"] == 1.0
    with pytest.raises(ValueError):
        mixed_bound(cfg, accept_rate=1.5, **kw)
    with pytest.raises(ValueError):
        mixed_bound(cfg, draft_tokens=-1, **kw)


def test_select_serve_defaults_spec_axis():
    from repro.core.autotune import select_serve_defaults

    out = select_serve_defaults("qwen2-1.5b", smoke=True, context_len=100)
    assert out["best"]["spec_k"] == 0  # default axis is non-speculative
    assert "spec@repetitive" not in out["table"][0]["criteria"]
    on = select_serve_defaults("qwen2-1.5b", smoke=True, context_len=100,
                               spec_ks=(0, 4))
    assert {r["spec_k"] for r in on["table"]} == {0, 4}
    assert all("spec@repetitive" in r["criteria"] for r in on["table"])
    # where the budget leaves draft room, speculation strictly lifts the
    # repetitive-goodput criterion over its k=0 twin
    knobs = ("token_budget", "prefill_chunk", "page_size", "kv_dtype",
             "scheduler", "n_devices", "host_pool_pages")
    for r in on["table"]:
        if r["spec_k"] == 4 and r["token_budget"] >= 2 * 8:
            twin = next(t for t in on["table"] if t["spec_k"] == 0
                        and all(t[k] == r[k] for k in knobs))
            assert (r["criteria"]["spec@repetitive"]
                    > twin["criteria"]["spec@repetitive"])
    assert on["best"]["spec_k"] == 4
