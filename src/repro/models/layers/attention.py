"""Attention: GQA self/cross attention with RoPE, sliding windows, and a
memory-safe chunked-softmax path (pure-jnp flash) for long sequences.

Layouts
-------
- q is kept grouped as (B, S, kvH, G, hd) with G = num_heads // num_kv_heads,
  so GQA never materializes repeated KV.
- Full-softmax path for short sequences / tests; q-chunked online path
  otherwise (peak scores bytes ~ B·kvH·G·q_chunk·T·4).
- Decode keeps a KV cache of capacity ``cache_len``; windowed layers use a
  circular buffer of size ``window`` with per-slot absolute positions, so a
  gemma3 local layer at 500k context stores only 1024 slots.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttnCfg
from repro.models.layers.common import dense_init
from repro.models.layers.embeddings import apply_rope
from repro.parallel.sharding import lshard

NEG_INF = -1e30


def init_attention(key, d: int, cfg: AttnCfg):
    """Weights are stored GROUPED — wq (D,kvH,G,hd), wo (kvH,G,hd,D) — so no
    reshape ever crosses the head dims.  With heads-TP active the G dim is
    'model'-sharded, and a flat<->grouped reshape across a sharded dim makes
    GSPMD fall back to full rematerialization (measured: +70 s/step of
    collectives on glm4)."""
    ks = jax.random.split(key, 4)
    kvH, hd = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // kvH
    p = {
        "wq": dense_init(ks[0], (d, kvH, G, hd)),
        "wk": dense_init(ks[1], (d, kvH, hd)),
        "wv": dense_init(ks[2], (d, kvH, hd)),
        "wo": dense_init(ks[3], (kvH, G, hd, d), in_axis_size=kvH * G * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((kvH, G, hd), jnp.float32)
        p["bk"] = jnp.zeros((kvH, hd), jnp.float32)
        p["bv"] = jnp.zeros((kvH, hd), jnp.float32)
    return p


def _project_q(params, cfg: AttnCfg, x, acc=None):
    """q in ``acc`` (the activation dtype by default), the dtype of the
    bias add."""
    dt = x.dtype
    q = jnp.einsum("bsd,dkgh->bskgh", x, params["wq"].astype(dt)).astype(acc or dt)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt).astype(q.dtype)
    return lshard(q, "act_batch", "act_seq", "act_kv_heads", "act_heads", None)


def _project_kv(params, cfg: AttnCfg, x, acc=None):
    dt = x.dtype
    k = jnp.einsum("bsd,dkh->bskh", x, params["wk"].astype(dt)).astype(acc or dt)
    v = jnp.einsum("bsd,dkh->bskh", x, params["wv"].astype(dt)).astype(acc or dt)
    if cfg.qkv_bias:
        k = k + params["bk"].astype(dt).astype(k.dtype)
        v = v + params["bv"].astype(dt).astype(v.dtype)
    k = lshard(k, "act_batch", "act_seq", "act_kv_heads", None)
    v = lshard(v, "act_batch", "act_seq", "act_kv_heads", None)
    return k, v


def _serving_qkv(params, cfg: AttnCfg, x, q_pos):
    """q, k, v of a serving step, in the activation dtype.  The bias add and
    the rotary embedding run in float32 and round once, after the rotation.
    In the activation dtype the compiler may keep the sum in float32 into
    the rotation or round it first, fusion by fusion, and a KV-head mesh's
    program and one device's program choose differently: the engine's
    outputs on four devices then part from one device's."""
    dt = x.dtype
    q = _project_q(params, cfg, x, jnp.float32)
    k, v = _project_kv(params, cfg, x, jnp.float32)
    if cfg.rope_theta is not None:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _out_proj(params, cfg: AttnCfg, o):
    # contraction over (kvH, G, hd): with G 'model'-sharded this is a local
    # dot + psum — no cross-shard reshape
    dt = o.dtype
    out = jnp.einsum("bskgh,kghd->bsd", o, params["wo"].astype(dt))
    return lshard(out, "act_batch", "act_seq", None)


def _out_proj_replicated(params, cfg: AttnCfg, o):
    # Serving-step variant: replicate the attention output BEFORE the
    # head-contracting einsum.  Under the engine's KV-head TP mesh this is
    # the single op that contracts across the sharded axis; left to GSPMD it
    # becomes a partial dot + psum, whose summation order depends on the
    # device count — replicating first (an exact all-gather) gives every
    # device one device's contraction.  The invariance suite finds engine
    # outputs bit-identical across 1/2/4 CPU devices at its small sizes;
    # that is not a promise at other sizes or on a TPU (see PERF.md).
    # No-op without a mesh.
    o = lshard(o, *([None] * o.ndim))
    return _out_proj(params, cfg, o)


def _mask_bias(q_pos, k_pos, causal: bool, window: Optional[int]):
    """(Sq, Sk) additive bias in f32."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        ok &= (q_pos[:, None] - k_pos[None, :]) < window
    ok &= k_pos[None, :] >= 0  # invalid (unwritten) cache slots carry pos=-1
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _softmax_attn(q, k, v, bias):
    """q: (B,Sq,kvH,G,hd)  k,v: (B,Sk,kvH,hd)  bias: (Sq,Sk) -> (B,Sq,kvH,G,hd)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k).astype(jnp.float32) * scale
    s = s + bias[None, None, None, :, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, v)
    return o


def _chunked_attn(q, k, v, q_positions, k_positions, causal, window, q_chunk):
    """lax.scan over query chunks; memory ~ one (Sq_chunk × Sk) score block."""
    B, S, kvH, G, hd = q.shape
    nq = S // q_chunk
    qc = q.reshape(B, nq, q_chunk, kvH, G, hd)
    qc = jnp.moveaxis(qc, 1, 0)  # (nq, B, C, kvH, G, hd)
    qp = q_positions.reshape(nq, q_chunk)

    def body(_, xs):
        q_i, qp_i = xs
        bias = _mask_bias(qp_i, k_positions, causal, window)
        o_i = _softmax_attn(q_i, k, v, bias)
        return None, o_i

    _, oc = jax.lax.scan(body, None, (qc, qp))
    o = jnp.moveaxis(oc, 0, 1).reshape(B, S, kvH, G, hd)
    return o


def attention_fwd(params, cfg: AttnCfg, x, *, positions=None, enc=None,
                  q_chunk: int = 128, use_flash: bool = False):
    """Full-sequence attention (train / prefill).

    enc: (B, T, D) encoder states for cross-attention (vision stub).
    """
    B, S, _ = x.shape
    q = _project_q(params, cfg, x)
    kv_src = enc if cfg.cross else x
    k, v = _project_kv(params, cfg, kv_src)
    T = k.shape[1]

    if positions is None:
        positions = jnp.arange(S)
    if cfg.rope_theta is not None and not cfg.cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    causal = cfg.causal and not cfg.cross
    k_positions = jnp.arange(T)

    if use_flash and causal and not cfg.cross and cfg.window is None and S == T:
        from repro.kernels import ops as kops

        o = kops.flash_attention_grouped(q, k, v)
    elif S <= 2 * q_chunk or S % q_chunk != 0:
        bias = _mask_bias(positions, k_positions, causal, cfg.window)
        o = _softmax_attn(q, k, v, bias)
    else:
        o = _chunked_attn(q, k, v, positions, k_positions, causal, cfg.window, q_chunk)
    return _out_proj(params, cfg, o)


# ---------------------------------------------------------------------------
# Decode (single token, KV cache)


def init_cache(cfg: AttnCfg, batch: int, max_len: int, dtype):
    """Cache capacity = min(max_len, window) (circular for windowed layers)."""
    cap = max_len if cfg.window is None else min(cfg.window, max_len)
    return {
        "k": jnp.zeros((batch, cap, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, cap, cfg.num_kv_heads, cfg.head_dim), dtype),
        "k_pos": jnp.full((cap,), -1, jnp.int32),
        "pos": jnp.zeros((), jnp.int32),
    }


def init_cross_cache(params, cfg: AttnCfg, enc):
    k, v = _project_kv(params, cfg, enc)
    return {"k": k, "v": v}


def prefill_cache(params, cfg: AttnCfg, cache, x, positions):
    """Write a full prompt into the cache (teacher-forced prefill)."""
    k, v = _project_kv(params, cfg, x)
    if cfg.rope_theta is not None:
        k = apply_rope(k, positions, cfg.rope_theta)
    S = x.shape[1]
    cap = cache["k"].shape[1]
    if S >= cap:  # keep last `cap` positions (windowed layer)
        k, v = k[:, -cap:], v[:, -cap:]
        kp = positions[-cap:]
        slots = kp % cap
        cache = dict(cache)
        cache["k"] = jnp.zeros_like(cache["k"]).at[:, slots].set(k)
        cache["v"] = jnp.zeros_like(cache["v"]).at[:, slots].set(v)
        cache["k_pos"] = jnp.full_like(cache["k_pos"], -1).at[slots].set(kp)
    else:
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1)
        cache["k_pos"] = cache["k_pos"].at[:S].set(positions)
    cache["pos"] = jnp.asarray(S, jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# Paged decode (per-slot positions, block-table KV pool)
#
# The serving engine's cache layout.  Global (window=None) layers store KV in
# a pool of fixed-size pages indexed through a per-slot block table, so a
# slot holding a short sequence only pins ceil(len/page) pages and the engine
# can admit more slots than ``B × cache_len`` worth of physical cache.
# Windowed layers keep per-slot circular buffers (their KV is bounded by the
# window, so paging buys nothing) but gain per-slot positions/validity.
# Unmapped block-table entries hold the OOB sentinel ``n_pages``: scatters to
# them are dropped, gathers clamp to an arbitrary page whose entries are then
# masked via ``kpos`` (-1 = never written).
#
# Prefix sharing rides on the same indirection: the serving engine may point
# several slots' block-table rows at ONE pool page (a cached shared prompt
# prefix, refcounted host-side).  Reads go through ptab and need nothing new;
# writes never target a shared page because a slot's first unmatched position
# always lands in a privately allocated page (copy-on-write duplicates a
# partially matched page before admission).  kpos for inherited positions is
# preset by ``reset_paged_slots`` so the reused KV is visible immediately.


# The pool's MEMORY REPRESENTATION is configurable (``kv_dtype``): float32 /
# bfloat16 pools store KV verbatim; int8 pools store symmetric int8 values
# plus one float32 scale per pool entry per KV head (``ks``/``vs``,
# (n_pages, kvH, 1, page)).  Pools are head-major — values (n_pages, kvH,
# page, hd) — so the Pallas kernel can DMA one head's (page, hd) tile (see
# ``kernels.flash_attention``).  The lifecycle is write-quantize -> paged
# read-dequant -> COW-with-scales: K/V rows are quantized ONCE as they are
# scattered into the pool (``_scatter_paged_kv``), every
# reader (prefill chunks, decode ticks, prefix hits, the Pallas kernels)
# dequantizes the same representation, and copy-on-write copies a page's
# scale row with its values.  Quantizing at write time means a page is
# byte-identical no matter which phase produced it — prefix hits on int8
# pools are exact replays of the cold path.


# the paged cache's pool leaves, shared by every slot: KV value pools and
# their int8 scale pools (the rest of a paged cache is per slot)
POOL_LEAVES = ("kp", "vp", "ks", "vs")


def kv_cache_dtype(kv_dtype, act_dtype):
    """Resolve a ``kv_dtype`` spec (None | str | dtype) to a jnp dtype.
    None means "follow the activation dtype" (the unquantized default)."""
    if kv_dtype is None:
        return jnp.dtype(act_dtype)
    return jnp.dtype(kv_dtype)


def init_paged_cache(cfg: AttnCfg, batch: int, cache_len: int, dtype, *,
                     page_size: int, n_pages: int, window_extra: int = 0,
                     kv_dtype=None):
    """Paged (global) or per-slot circular (windowed) decode cache.

    ``window_extra`` over-provisions windowed buffers: a C-token chunk write
    evicts the C oldest entries, so the earliest query in the chunk (which
    still needs keys up to ``window`` behind it) requires capacity
    ``window + C - 1`` — callers doing C-token chunked prefill must pass
    ``window_extra = C - 1``.  Stale entries beyond the window stay masked
    via ``kpos``, so extra capacity never changes attention results.

    ``kv_dtype`` (None | "float32" | "bfloat16" | "int8") sets the PAGED
    pool's storage dtype; int8 pools add per-entry-per-head scale pools
    ``ks``/``vs``.  Windowed circular buffers always store the activation
    dtype — their footprint is bounded by the window, so quantizing them
    buys little, and models with windowed layers serve with prefix sharing
    off anyway.
    """
    kvH, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.window is not None:
        cap = min(cfg.window, cache_len) + window_extra
        return {
            "k": jnp.zeros((batch, cap, kvH, hd), dtype),
            "v": jnp.zeros((batch, cap, kvH, hd), dtype),
            "kpos": jnp.full((batch, cap), -1, jnp.int32),
            "slen": jnp.zeros((batch,), jnp.int32),
        }
    kvd = kv_cache_dtype(kv_dtype, dtype)
    pps = -(-cache_len // page_size)  # block-table width (pages per slot)
    cache = {
        "kp": jnp.zeros((n_pages, kvH, page_size, hd), kvd),
        "vp": jnp.zeros((n_pages, kvH, page_size, hd), kvd),
        "ptab": jnp.full((batch, pps), n_pages, jnp.int32),
        "kpos": jnp.full((batch, pps * page_size), -1, jnp.int32),
        "slen": jnp.zeros((batch,), jnp.int32),
    }
    if kvd == jnp.int8:
        cache["ks"] = jnp.zeros((n_pages, kvH, 1, page_size), jnp.float32)
        cache["vs"] = jnp.zeros((n_pages, kvH, 1, page_size), jnp.float32)
    return cache


def _paged_masked_attn(q, k, v, kpos, q_pos, window):
    """Per-slot masked softmax: q (B,C,kvH,G,hd), k/v (B,T,kvH,hd),
    kpos (B,T), q_pos (B,C) -> (B,C,kvH,G,hd)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k).astype(jnp.float32) * scale
    ok = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        ok &= (q_pos[:, :, None] - kpos[:, None, :]) < window
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    p = jnp.where(ok[:, None, None, :, :], p, 0.0)
    return jnp.einsum("bkgqt,btkd->bqkgd", p, v)


def _scatter_paged_kv(cache, k_new, v_new, page, off, layer=None):
    """Scatter new K/V rows into the head-major pools at ``[page, :, off]``
    — the serving step's single write path.
    k_new/v_new: (..., kvH, hd) with ``page``/``off`` of shape (...).
    ``layer`` indexes pools stacked over a scanned stage's layers, (L,
    n_pages, ...); None for one layer's pools.  int8 pools quantize on
    write: values into ``kp``/``vp`` and their scales into ``ks``/``vs`` at
    ``[page, :, 0, off]``; OOB sentinel pages drop the write either way.
    Each pool takes one scatter over (token, KV head) pairs, each an
    ``hd``-long row along the pool's minor dim: the TPU compiler writes
    that in the pool's own layout, in place, where a scatter whose window
    spans the heads would have it copy the whole pool into another layout
    and back; under a KV-head mesh each device writes its own heads.  Mutates
    the caller's cache dict (callers own a fresh copy)."""
    from repro.kernels import ops as kops

    kvH = k_new.shape[-2]
    head = jnp.arange(kvH)
    at = (() if layer is None else (layer,)) + (
        page[..., None], head, off[..., None])
    for name, rows in (("k", k_new), ("v", v_new)):
        if name + "s" in cache:  # int8 pool: fused quantize-on-write
            rows, s = kops.quantize_kv(rows)
            cache[name + "s"] = cache[name + "s"].at[
                at[:-1] + (0, at[-1])].set(s, mode="drop")
        pool = cache[name + "p"]
        cache[name + "p"] = pool.at[at].set(rows.astype(pool.dtype),
                                            mode="drop")


def _gather_paged_kv(cache, dtype, layer=None):
    """Gather the whole block-table context from the pool, dequantizing int8
    pools against their per-entry scales (the jnp oracle of the fused kernel
    path).  ``layer`` as in ``_scatter_paged_kv``.  Returns (k, v) of shape
    (B, pps * page, kvH, hd) in ``dtype``.
    """
    from repro.kernels import ops as kops

    at = (cache["ptab"],) if layer is None else (layer, cache["ptab"])

    def gather(name):
        x = cache[name + "p"].at[at].get(mode="clip")
        if name + "s" in cache:  # (B, pps, kvH, 1, P) -> (B, pps, kvH, P)
            s = cache[name + "s"].at[at].get(mode="clip")[..., 0, :]
            x = kops.dequantize_kv(x, s, dtype)
        B, pps, kvH, P, hd = x.shape  # head-major pages -> token-major
        return jnp.swapaxes(x, 2, 3).reshape(B, pps * P, kvH, hd).astype(dtype)

    return gather("k"), gather("v")


def ragged_attention_step(params, cfg: AttnCfg, x, cache, slot, q_pos, valid,
                          *, flash_decode: bool = False, layer=None):
    """One ragged serving step: a flat pack of T tokens from arbitrary slots.

    x: (1, T, D) hidden pack; slot/q_pos/valid: (T,) per-token slot index,
    absolute position, and validity.  Any mix of prefill-chunk tokens and
    decode tokens rides in one pack — the cache write and the per-token
    causal mask (``kpos <= q_pos``) make intra-pack causality fall out of the
    same machinery as cross-tick causality, so a prefill chunk and the decode
    tokens of other slots coexist in a single program.  Invalid tokens
    scatter nowhere and their outputs are garbage the engine never reads.
    ``layer``: this layer's index into pool leaves stacked over a scanned
    stage (``transformer.stage_step_ragged``); None when they are its own.
    """
    T = x.shape[1]
    # (T,kvH,G,hd), (T,kvH,hd) x2
    q, k_new, v_new = (t[0] for t in _serving_qkv(params, cfg, x, q_pos[None]))

    paged = "kp" in cache
    cache = dict(cache)
    B = cache["slen"].shape[0]
    if paged:
        n_pages, _, P, _ = cache["kp"].shape[-4:]
        pps = cache["ptab"].shape[-1]
        page_slot = jnp.clip(q_pos // P, 0, pps - 1)
        page = cache["ptab"][slot, page_slot]  # (T,)
        page = jnp.where(valid, page, n_pages)  # OOB -> scatter dropped
        off = q_pos % P
        _scatter_paged_kv(cache, k_new, v_new, page, off, layer)
        Tc = pps * P
        idx = jnp.where(valid, q_pos, Tc)
    else:
        cap = cache["k"].shape[1]
        # a slot's pack tokens are contiguous positions: when they exceed
        # ``cap`` the circular buffer wraps within one scatter, so keep only
        # the last ``cap`` writes per slot (duplicate scatter indices have
        # unspecified order).  row_max is a per-slot segment max.
        row_max = jnp.full((B,), -1, jnp.int32).at[slot].max(
            jnp.where(valid, q_pos, -1), mode="drop")
        keep = valid & (q_pos > row_max[slot] - cap)
        idx = jnp.where(keep, q_pos % cap, cap)
        cache["k"] = cache["k"].at[slot, idx].set(k_new, mode="drop")
        cache["v"] = cache["v"].at[slot, idx].set(v_new, mode="drop")
        Tc = cap
    cache["kpos"] = cache["kpos"].at[slot, idx].set(q_pos, mode="drop")
    cache["slen"] = cache["slen"].at[slot].max(
        jnp.where(valid, q_pos + 1, 0), mode="drop")

    if paged and flash_decode:
        # TP entry point: shard_maps the Pallas kernel over the KV-head axis
        # under the serving mesh, plain kernel call otherwise
        from repro.serve.decode_attention import tp_ragged_paged_flash

        lens = jnp.where(valid, q_pos + 1, 0).astype(jnp.int32)
        o = tp_ragged_paged_flash(q, cache["kp"], cache["vp"],
                                  cache["ptab"], slot, lens,
                                  ks=cache.get("ks"), vs=cache.get("vs"),
                                  layer=layer)[None]
        return _out_proj_replicated(params, cfg, o), cache

    if paged:
        k_all, v_all = _gather_paged_kv(cache, q.dtype, layer)
    else:
        k_all, v_all = cache["k"], cache["v"]
    # gather each token's slot context and run T single-query attentions:
    # _paged_masked_attn with the pack as the batch axis and C == 1
    k_tok = k_all[slot]  # (T,Tc,kvH,hd)
    v_tok = v_all[slot]
    kpos_tok = cache["kpos"][slot]  # (T,Tc)
    o = _paged_masked_attn(q[:, None], k_tok, v_tok, kpos_tok,
                           q_pos[:, None], cfg.window)  # (T,1,kvH,G,hd)
    o = jnp.moveaxis(o, 1, 0)  # (1,T,kvH,G,hd)
    return _out_proj_replicated(params, cfg, o), cache


def attention_decode(params, cfg: AttnCfg, x, cache, *, sp_decode: bool = False):
    """x: (B,1,D). Returns (out (B,1,D), new_cache)."""
    B = x.shape[0]
    pos = cache["pos"] if not cfg.cross else None
    q = _project_q(params, cfg, x)  # (B,1,kvH,G,hd)

    if cfg.cross:
        k, v = cache["k"], cache["v"]
        bias = jnp.zeros((1, k.shape[1]), jnp.float32)
        o = _softmax_attn(q, k, v, bias)
        return _out_proj(params, cfg, o), cache

    k_new, v_new = _project_kv(params, cfg, x)  # (B,1,kvH,hd)
    if cfg.rope_theta is not None:
        ppos = pos[None]
        q = apply_rope(q, ppos, cfg.rope_theta)
        k_new = apply_rope(k_new, ppos, cfg.rope_theta)

    cap = cache["k"].shape[1]
    slot = pos % cap
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, 1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, 1)
    k_pos = jax.lax.dynamic_update_slice_in_dim(
        cache["k_pos"], pos[None].astype(jnp.int32), slot, 0
    )
    new_cache = {"k": k, "v": v, "k_pos": k_pos, "pos": pos + 1}

    if sp_decode:
        from repro.serve.decode_attention import sp_flash_decode

        o = sp_flash_decode(q, k, v, k_pos, pos, window=cfg.window)
    else:
        qp = pos[None]
        bias = _mask_bias(qp, k_pos, True, cfg.window)
        o = _softmax_attn(q, k, v, bias)
    return _out_proj(params, cfg, o), new_cache
