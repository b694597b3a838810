"""int8 quantized paged KV cache: quantize/dequant round-trip bounds, the
engine-level fp32-vs-int8 greedy top-1 agreement (the headline acceptance
bar), flash-kernel fused-dequant parity, warm-prefix/COW correctness on
quantized pools (scales travel with their pages), byte-budget pool sizing
(~2x or better resident pages), hot-loop buffer donation (no-copy pool
updates, asserted by pointer identity), dtype-aware roofline bytes, and the
backend-aware Pallas ``interpret`` default."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.models import model as M
from repro.serve.engine import ServeEngine, kv_bytes_per_token, kv_page_bytes

KEY = jax.random.PRNGKey(0)
CACHE = 64


@pytest.fixture(scope="module")
def qwen():
    # float32 activations keep greedy argmax stable across batching layouts;
    # the KV pool dtype is the engine knob under test
    cfg = get_config("qwen2-1.5b", smoke=True).replace(dtype="float32")
    return cfg, M.init_params(KEY, cfg)


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, L) for L in lens]


def _serve(cfg, params, prompts, max_tokens=4, **kw):
    kw.setdefault("batch_size", 3)
    kw.setdefault("cache_len", CACHE)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("token_budget", 32)
    eng = ServeEngine(params, cfg, **kw)
    uids = [eng.submit(p, max_tokens=max_tokens) for p in prompts]
    return eng, uids, eng.run()


# ---------------------------------------------------------------------------
# Quantize/dequant primitives


def test_quantize_roundtrip_error_bound():
    """Per-row symmetric int8: reconstruction error of every element is at
    most half a quantization step (absmax/254), across magnitudes from
    subnormal-ish rows to large ones."""
    rng = np.random.RandomState(0)
    for scale_mag in (1e-4, 1.0, 300.0):
        x = jnp.asarray(rng.randn(6, 4, 32) * scale_mag, jnp.float32)
        q, s = kops.quantize_kv(x)
        assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
        back = kops.dequantize_kv(q, s)
        absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        bound = absmax / 254.0 + 1e-12
        assert bool(jnp.all(jnp.abs(back - x) <= bound)), (
            float(jnp.max(jnp.abs(back - x))), float(jnp.max(bound)))


def test_quantize_zero_rows_roundtrip_to_zero():
    q, s = kops.quantize_kv(jnp.zeros((3, 2, 16)))
    assert bool(jnp.all(q == 0))
    assert bool(jnp.all(kops.dequantize_kv(q, s) == 0.0))


def test_quantize_preserves_row_absmax_sign_and_extremes():
    """The absmax element of every row maps to exactly +/-127 (symmetric
    scaling uses the full int8 range)."""
    x = jnp.asarray(np.random.RandomState(1).randn(8, 2, 16), jnp.float32)
    q, _ = kops.quantize_kv(x)
    assert int(jnp.max(jnp.abs(q))) == 127


def test_copy_pages_carries_scale_rows():
    """COW on an int8 pool must copy a page's scale row with its values —
    `copy_pages` on a scale pool duplicates rows exactly."""
    rng = np.random.RandomState(2)
    # (n_pages, kvH, 1, page)
    ks = jnp.asarray(rng.rand(6, 2, 1, 4), jnp.float32)
    src = jnp.asarray([1, 6], jnp.int32)  # second pair = sentinel no-op
    dst = jnp.asarray([3, 6], jnp.int32)
    out = kops.copy_pages(ks, src, dst)
    assert bool(jnp.all(out[3] == ks[1]))
    assert bool(jnp.all(out[:3] == ks[:3])) and bool(jnp.all(out[4:] == ks[4:]))


# ---------------------------------------------------------------------------
# Engine-level acceptance: agreement, parity, warm paths


def test_int8_engine_top1_agreement_with_fp32(qwen):
    """The headline bar: greedy int8 serving agrees with fp32 on >= 99% of
    emitted tokens over the smoke-sweep style prompt mix."""
    cfg, params = qwen
    prompts = _prompts(cfg, [5, 19, 11, 26, 8, 14, 33, 7], seed=90)

    def run(kvd):
        _, uids, got = _serve(cfg, params, prompts, max_tokens=6,
                              batch_size=3, kv_dtype=kvd)
        return [t for u in uids for t in got[u]]

    fp32, int8 = run(None), run("int8")
    agree = np.mean([a == b for a, b in zip(fp32, int8)])
    assert agree >= 0.99, f"top-1 agreement {agree:.3f} < 0.99"


def test_int8_flash_kernel_matches_jnp_path(qwen):
    """The fused-dequant Pallas kernels and the jnp dequant oracle read the
    SAME representation: token-identical outputs."""
    cfg, params = qwen
    prompts = _prompts(cfg, [5, 19, 11], seed=91)
    _, u1, r1 = _serve(cfg, params, prompts, batch_size=2, kv_dtype="int8")
    _, u2, r2 = _serve(cfg, params, prompts, batch_size=2, kv_dtype="int8",
                       flash_decode=True)
    assert [r1[u] for u in u1] == [r2[u] for u in u2]


def test_bfloat16_pool_serves(qwen):
    """The middle kv_dtype: a bf16 pool (half the bytes, no scales) serves
    the same traffic end to end."""
    cfg, params = qwen
    prompts = _prompts(cfg, [9, 17], seed=92)
    eng, uids, got = _serve(cfg, params, prompts, kv_dtype="bfloat16")
    assert all(len(got[u]) == 4 for u in uids)
    assert eng.stats["kv_dtype"] == "bfloat16"
    assert eng.stats["kv_bytes_per_token"] * 2 == kv_bytes_per_token(
        cfg, "float32")


def test_int8_warm_prefix_token_identical_to_cold(qwen):
    """Prefix hits on an int8 pool replay the quantized pages byte-for-byte
    (quantize-at-write): warm outputs == cold outputs, with hits."""
    cfg, params = qwen
    [shared] = _prompts(cfg, [32], seed=93)
    prompts = [np.concatenate([shared, s])
               for s in _prompts(cfg, [5, 7], seed=94)]
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=8, prefill_chunk=16, token_budget=32,
                      kv_dtype="int8")
    u1 = [eng.submit(p, max_tokens=4) for p in prompts]
    cold = eng.run()
    u2 = [eng.submit(p, max_tokens=4) for p in prompts]
    warm = eng.run()
    assert [cold[u] for u in u1] == [warm[u] for u in u2]
    assert eng.stats["prefix_hits"] >= 2
    assert eng.stats["prefix_tokens_reused"] >= 2 * 32
    assert eng.stats["traces"] == 1  # quantization lives inside the one trace


@settings(max_examples=6, deadline=None)
@given(share=st.sampled_from([3, 9, 16, 21, 27]),
       page=st.sampled_from([4, 8]))
def test_cow_divergence_int8_copies_scales_never_perturbs_sibling(
        qwen, share, page):
    """Property: COW on an int8 pool duplicates values AND scale rows, so a
    diverging request (a) matches a cold-pool int8 run of itself and (b) the
    shared sibling re-served afterwards is bit-identical to its own cold
    output — the divergent write never leaked into shared pages or their
    scales."""
    cfg, params = qwen
    rng = np.random.RandomState(95)
    a = rng.randint(0, cfg.vocab_size, 28)
    b = a.copy()
    b[share:] = (b[share:] + 1 + rng.randint(0, 100)) % cfg.vocab_size

    def cold_solo(p):
        _, [u], got = _serve(cfg, params, [p], batch_size=1, page_size=page,
                             prefill_chunk=8, token_budget=16,
                             kv_dtype="int8")
        return got[u]

    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE,
                      page_size=page, prefill_chunk=8, token_budget=16,
                      kv_dtype="int8")
    ua = eng.submit(a, max_tokens=4)
    ra = eng.run()
    ub = eng.submit(b, max_tokens=4)
    rb = eng.run()
    ua2 = eng.submit(a, max_tokens=4)  # sibling again, warm, post-COW
    ra2 = eng.run()
    assert ra[ua] == cold_solo(a)
    assert rb[ub] == cold_solo(b)
    assert ra2[ua2] == ra[ua]
    reusable = min(share, (len(a) // page) * page)
    assert eng.stats["cow_copies"] >= (1 if reusable % page else 0)
    assert (eng._ref == 0).all()
    assert eng.reclaimable_pages == eng.n_pages


# ---------------------------------------------------------------------------
# Byte-budget pool sizing / stats


def test_int8_doubles_resident_pages_in_same_byte_budget(qwen):
    """The working-set claim: at the default (byte-denominated) page budget
    an int8 pool holds >= 2x the pages of the fp32 pool, and the per-token
    KV bytes drop by >= 2x (values + scales accounted)."""
    cfg, params = qwen
    e32 = ServeEngine(params, cfg, batch_size=3, cache_len=CACHE, page_size=8)
    e8 = ServeEngine(params, cfg, batch_size=3, cache_len=CACHE, page_size=8,
                     kv_dtype="int8")
    assert e8.n_pages >= 2 * e32.n_pages
    assert e32.stats["kv_bytes_per_token"] >= 2 * e8.stats["kv_bytes_per_token"]
    # same byte budget: the int8 pool's total footprint never exceeds fp32's
    assert e8.stats["kv_pool_bytes"] <= e32.stats["kv_pool_bytes"]
    assert e8.stats["kv_dtype"] == "int8"
    # helper consistency: page bytes scale linearly in page_size
    assert kv_page_bytes(cfg, 8, "int8") == 8 * kv_page_bytes(cfg, 1, "int8")


def test_int8_admits_more_concurrent_requests_at_equal_bytes(qwen):
    """At a pool byte budget that throttles fp32 to ~1 in-flight request,
    the int8 pool (same bytes) serves the wave with strictly more slots
    concurrently busy — the admission-throughput half of the claim."""
    cfg, params = qwen
    prompts = _prompts(cfg, [12] * 4, seed=96)
    fp32_pages = -(-(12 + 8) // 8) + 1  # one request + one page of slack
    bytes_budget = fp32_pages * kv_page_bytes(cfg, 8, "float32")
    int8_pages = bytes_budget // kv_page_bytes(cfg, 8, "int8")
    assert int8_pages >= 2 * fp32_pages

    def peak_busy(kvd, pages):
        eng = ServeEngine(params, cfg, batch_size=4, cache_len=CACHE,
                          page_size=8, prefill_chunk=16, token_budget=32,
                          max_pages=int(pages), kv_dtype=kvd,
                          prefix_cache=False)
        uids = [eng.submit(p, max_tokens=8) for p in prompts]
        peak = 0
        while not eng.idle:
            eng.tick()
            peak = max(peak, sum(s is not None for s in eng.slots))
        return peak

    assert peak_busy("int8", int8_pages) > peak_busy(None, fp32_pages)


def test_invalid_kv_dtype_rejected(qwen):
    cfg, params = qwen
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, batch_size=2, cache_len=32, page_size=8,
                    kv_dtype="int4")


# ---------------------------------------------------------------------------
# Hot-loop buffer donation (no-copy pool updates)


def test_ragged_step_donates_pools_in_place(qwen):
    """The serve step is jit'd with the state donated: on backends that
    support donation the page pools (and int8 scale pools) are updated IN
    PLACE — the output state's buffers are the input state's buffers, so the
    hot loop never copies the pool.  The pointer-identity check is the
    shared ``analysis.contracts`` helper, so this runtime assert and the
    static donation proof read the same pool-leaf list and cannot drift."""
    from repro.analysis.contracts import assert_donated, pool_buffer_pointers

    cfg, params = qwen
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=CACHE, page_size=8,
                      prefill_chunk=16, token_budget=32, kv_dtype="int8")
    eng.submit(_prompts(cfg, [20], seed=97)[0], max_tokens=8)
    eng.tick()  # compile + first real step
    before = pool_buffer_pointers(eng._state)
    if before is None:
        pytest.skip("backend exposes no buffer pointers")
    assert before  # int8 paged model: pools must exist
    eng.tick()
    # "undonated" (backend donated nothing) is tolerated; a PARTIAL
    # donation raises inside assert_donated — that is always a bug
    if assert_donated(before, eng._state) == "undonated":
        pytest.skip("backend does not donate buffers")


# ---------------------------------------------------------------------------
# Roofline / autotune dtype awareness


def test_mixed_bound_int8_halves_decode_side_bytes():
    """Regression: the analytic blend's KV traffic with int8 KV is at most
    half the fp32 traffic for the same mix (values + amortized scales), and
    the bound's tokens/s never degrades."""
    from repro.core.roofline import mixed_bound

    cfg = get_config("qwen2-1.5b", smoke=True).replace(dtype="float32")
    kw = dict(n_decode=8, n_prefill=24, context_len=192, page_size=16)
    r32 = mixed_bound(cfg, kv_dtype="float32", **kw)
    r8 = mixed_bound(cfg, kv_dtype="int8", **kw)
    assert r8["kv_read_bytes"] <= 0.5 * r32["kv_read_bytes"]
    assert r8["kv_write_bytes"] <= 0.5 * r32["kv_write_bytes"]
    assert r8["tokens_per_s"] >= r32["tokens_per_s"]
    # bf16 sits exactly at half fp32 (no scale overhead)
    r16 = mixed_bound(cfg, kv_dtype="bfloat16", **kw)
    assert r16["kv_read_bytes"] == pytest.approx(0.5 * r32["kv_read_bytes"])


def test_decode_bound_kv_dtype_only_touches_global_layers():
    """Windowed layers keep activation-dtype circular buffers: on a hybrid
    (gemma3: 5 local + 1 global) the int8 saving applies only to the global
    layer's bytes."""
    from repro.core.roofline import decode_bound

    cfg = get_config("gemma3-4b", smoke=True).replace(dtype="float32")
    r32 = decode_bound(cfg, batch=4, context_len=64, page_size=8,
                       kv_dtype="float32")
    r8 = decode_bound(cfg, batch=4, context_len=64, page_size=8,
                      kv_dtype="int8")
    assert r8["kv_bytes"] < r32["kv_bytes"]  # global layer shrank...
    # ...but the windowed layers' bytes keep the pools from a full 2x cut
    assert r8["kv_bytes"] > 0.25 * r32["kv_bytes"]


def test_select_serve_defaults_tunes_kv_dtype():
    """The tuned-once serving config now picks the memory representation:
    kv_dtype is on the swept axis and lands in the emitted config (int8
    dominates every memory-bound criterion, so it must win when offered)."""
    from repro.core.autotune import select_serve_defaults

    out = select_serve_defaults("qwen2-1.5b", smoke=True, context_len=100)
    assert out["best"]["kv_dtype"] in ("float32", "bfloat16", "int8")
    assert all("kv_dtype" in r for r in out["table"])
    only8 = select_serve_defaults("qwen2-1.5b", smoke=True, context_len=100,
                                  kv_dtypes=("int8",))
    assert only8["best"]["kv_dtype"] == "int8"


# ---------------------------------------------------------------------------
# Backend-aware Pallas interpret default


def test_default_interpret_backend_and_env(monkeypatch):
    """False iff the backend is a real TPU, and no environment variable can
    flip it: a chip run must never silently measure the interpreter."""
    from repro.kernels.ops import default_interpret

    want = jax.default_backend() != "tpu"
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert default_interpret() is want
    for env in ("0", "1", "true", "false", "garbage"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", env)
        assert default_interpret() is want
