"""Model entry points: init / forward / loss / prefill / decode.

All functions are pure; params and decode state are plain pytrees.  The
vision and audio frontends are stubs — inputs arrive as precomputed patch /
frame embeddings (see DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg
from repro.models import transformer as tfm
from repro.models.layers import embeddings as emb
from repro.models.layers.norms import init_rmsnorm, rmsnorm
from repro.parallel.sharding import lshard

MOE_LB_WEIGHT = 0.01
MOE_Z_WEIGHT = 1e-3


# ---------------------------------------------------------------------------
# Init


def init_params(key, cfg: ModelCfg) -> Dict:
    ks = jax.random.split(key, 4 + len(cfg.stages))
    p: Dict = {}
    if cfg.frontend == "audio":
        # stub frontend: project precomputed frame features (feat dim = d/2)
        p["frontend"] = emb.init_frontend(ks[0], cfg.d_model // 2, cfg.d_model)
    else:
        p["embed"] = emb.init_tok_embed(ks[0], cfg.vocab_size, cfg.d_model)
    if cfg.frontend == "vision":
        p["frontend"] = emb.init_frontend(ks[1], cfg.d_model // 2, cfg.d_model)
    p["stages"] = [tfm.init_stage(ks[3 + i], cfg, st) for i, st in enumerate(cfg.stages)]
    p["final_norm"] = init_rmsnorm(cfg.d_model)
    if not cfg.tie_embeddings or cfg.frontend == "audio":
        p["head"] = emb.init_out_head(ks[2], cfg.d_model, cfg.vocab_size)
    dt = jnp.dtype(cfg.param_dtype)
    if dt != jnp.float32:
        p = jax.tree.map(lambda x: x.astype(dt) if x.dtype == jnp.float32 else x, p)
    return p


# ---------------------------------------------------------------------------
# Forward / loss


def _embed_inputs(params, cfg: ModelCfg, batch):
    dt = jnp.dtype(cfg.dtype)
    if cfg.frontend == "audio":
        x = emb.apply_frontend(params["frontend"], batch["feats"], dt)
    else:
        x = emb.embed_tokens(params["embed"], batch["tokens"], dt)
    if cfg.abs_pos == "sinusoidal":
        x = x + emb.sinusoidal_pos(x.shape[1], cfg.d_model, dt)
    enc = None
    if cfg.frontend == "vision":
        enc = emb.apply_frontend(params["frontend"], batch["img_feats"], dt)
        enc = lshard(enc, "act_batch", None, None)
    return lshard(x, "act_batch", "act_seq", None), enc


def forward(params, cfg: ModelCfg, batch) -> Tuple[jax.Array, Dict]:
    """-> (logits (B,S,V) vocab-sharded, aux dict)."""
    x, enc = _embed_inputs(params, cfg, batch)
    S = x.shape[1]
    positions = jnp.arange(S)
    aux = dict(tfm.ZERO_AUX)
    for st, sp in zip(cfg.stages, params["stages"]):
        x, a = tfm.stage_fwd(sp, cfg, st, x, positions=positions, enc=enc)
        aux = tfm._add_aux(aux, a)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    tied = params["embed"]["tok_embed"] if (cfg.tie_embeddings and "embed" in params) else None
    logits = emb.logits_from_hidden(params.get("head", {}), x, tied_embed=tied)
    return logits, aux


def _xent(logits, labels):
    """CE over vocab-sharded logits without gathering the vocab axis.

    logits: (B,S,V) sharded P(batch, None, 'model'); labels: (B,S) int32.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    V = logits.shape[-1]
    hit = jnp.equal(labels[..., None], jax.lax.broadcasted_iota(jnp.int32, lf.shape, 2))
    tgt = jnp.sum(jnp.where(hit, lf, 0.0), axis=-1)
    return lse - tgt  # (B,S)


def loss_fn(params, cfg: ModelCfg, batch) -> Tuple[jax.Array, Dict]:
    logits, aux = forward(params, cfg, batch)
    per_tok = _xent(logits, batch["labels"])
    if "loss_mask" in batch:
        mask = batch["loss_mask"].astype(jnp.float32)
        loss = jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        loss = jnp.mean(per_tok)
    total = (loss + MOE_LB_WEIGHT * aux["moe_lb_loss"]
             + MOE_Z_WEIGHT * aux["moe_z_loss"])
    metrics = {"ce_loss": loss, **aux}
    return total, metrics


# ---------------------------------------------------------------------------
# Decode


def init_decode_state(params, cfg: ModelCfg, batch: int, cache_len: int,
                      enc_feats=None) -> Dict:
    """Fresh per-layer caches/states for autoregressive decoding."""
    dt = jnp.dtype(cfg.dtype)
    enc = None
    if cfg.frontend == "vision":
        enc = emb.apply_frontend(params["frontend"], enc_feats, dt)
    states = [tfm.init_stage_state(sp, cfg, st, batch, cache_len, dt, enc)
              for st, sp in zip(cfg.stages, params["stages"])]
    return {"layers": states, "pos": jnp.zeros((), jnp.int32)}


def decode_step(params, cfg: ModelCfg, state, tokens_t, *,
                sp_decode: bool = False) -> Tuple[jax.Array, Dict]:
    """tokens_t: (B,1) int32 -> (logits (B,1,V), new state)."""
    dt = jnp.dtype(cfg.dtype)
    x = emb.embed_tokens(params["embed"], tokens_t, dt)
    if cfg.abs_pos == "sinusoidal":
        x = x + emb.sinusoidal_pos(1, cfg.d_model, dt, offset=state["pos"])
    new_layers = []
    for st, sp, ss in zip(cfg.stages, params["stages"], state["layers"]):
        x, ns = tfm.stage_decode(sp, cfg, st, x, ss, sp_decode=sp_decode)
        new_layers.append(ns)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    tied = params["embed"]["tok_embed"] if cfg.tie_embeddings else None
    logits = emb.logits_from_hidden(params.get("head", {}), x, tied_embed=tied)
    return logits, {"layers": new_layers, "pos": state["pos"] + 1}


# ---------------------------------------------------------------------------
# Paged serving (per-slot positions; chunked prefill and decode share one step)


def init_paged_state(params, cfg: ModelCfg, batch: int, cache_len: int, *,
                     page_size: int, n_pages: int, window_extra: int = 0,
                     kv_dtype=None) -> Dict:
    """Decode state for the paged serving engine: global-attention layers get
    block-table-indexed KV pools (``n_pages`` pages of ``page_size``),
    windowed layers per-slot circular buffers, recurrent mixers per-row
    states.  Every slot tracks its own position — no lock-step ``pos``.

    ``kv_dtype`` (None | "float32" | "bfloat16" | "int8") selects the paged
    pools' storage representation; int8 pools carry per-entry-per-head
    scale pools (see ``attention.init_paged_cache``).

    ``window_extra`` must be ``prefill_chunk - 1`` when chunked prefill is
    used (see ``attention.init_paged_cache``)."""
    if cfg.frontend is not None or cfg.is_encoder:
        raise NotImplementedError("paged serving covers decoder token models")
    dt = jnp.dtype(cfg.dtype)
    states = [tfm.init_stage_state_paged(sp, cfg, st, batch, cache_len, dt,
                                         page_size=page_size, n_pages=n_pages,
                                         window_extra=window_extra,
                                         kv_dtype=kv_dtype)
              for st, sp in zip(cfg.stages, params["stages"])]
    return {"layers": states}


def ragged_step(params, cfg: ModelCfg, state, tokens, slot, q_pos, seq_idx,
                valid, logit_idx, *, width: int,
                flash_decode: bool = False):
    """One ragged token-budget step: T tokens from any mix of slots/phases.

    The single compiled program of the ragged serving engine.  tokens /
    slot / q_pos / seq_idx / valid are flat (T,) vectors — each entry is one
    token of one slot at one absolute position (seq_idx is its intra-slot
    ordinal within the pack, for the recurrent repack; the scheduler packs at
    most ``width`` tokens per slot, in position order).  Prefill chunks and
    decode tokens are indistinguishable at this level; causality between
    them falls out of the per-token position masks.

    logit_idx: (B,) index into the pack of each slot's sampled token (T ==
    no sample this tick; those rows return garbage logits the engine
    ignores).  Returns (logits (B, V), new state).  A speculative engine
    passes (B, R) instead — row 0 is the slot's base decode token and rows
    1..R-1 its packed draft tokens — and gets (B, R, V) back: one forward
    verifies the whole draft chain, the engine samples row j to check
    draft j.  Unused rows carry T like the 1-D case.  The shape is fixed
    per engine, so either way there is exactly one compiled program.

    Callers must jit this with the state donated
    (``serve_step.STATE_DONATE_ARGNUM``): the KV page pools (and, for int8
    pools, their scale pools) plus the recurrent-state carries dominate the
    pytree, and donation turns every tick's pool update into an in-place
    scatter instead of a whole-pool copy.
    """
    dt = jnp.dtype(cfg.dtype)
    x = emb.embed_tokens(params["embed"], tokens[None], dt)  # (1,T,D)
    if cfg.abs_pos == "sinusoidal":
        x = x + emb.sinusoidal_at(q_pos, cfg.d_model, dt)
    new_layers = []
    for st, sp, ss in zip(cfg.stages, params["stages"], state["layers"]):
        x, ns = tfm.stage_step_ragged(sp, cfg, st, x, ss, slot, q_pos,
                                      seq_idx, valid, width=width,
                                      flash_decode=flash_decode)
        new_layers.append(ns)
    # gather only the sampled tokens before the LM head: the pack is T wide
    # but at most B slots (times R verify rows) sample per tick, so the
    # head runs at (B, V) / (B*R, V) instead of (T, V)
    flat_idx = logit_idx.reshape(-1)
    sel = jnp.take(x[0], jnp.minimum(flat_idx, x.shape[1] - 1), axis=0)
    sel = rmsnorm(params["final_norm"], sel[:, None, :], cfg.norm_eps)
    tied = params["embed"]["tok_embed"] if cfg.tie_embeddings else None
    logits = emb.logits_from_hidden(params.get("head", {}), sel,
                                    tied_embed=tied)
    logits = logits[:, 0]
    if logit_idx.ndim == 2:
        logits = logits.reshape(logit_idx.shape + logits.shape[-1:])
    return logits, {"layers": new_layers}


def reset_paged_slots(cfg: ModelCfg, state, init_state, mask, ptab_rows,
                      prefix_len) -> Dict:
    """Admission/eviction: for slots where ``mask`` is set, install the
    host-allocated block-table rows and restore all other per-row state from
    the fresh-init template (KV pools are shared and untouched — they double
    as the cross-request prefix cache).  ``prefix_len`` (B,) marks how many
    leading positions each admitted slot inherits from shared prefix pages:
    their kpos/slen come up live so the reused KV is visible immediately
    (see ``transformer.reset_stage_slots``)."""
    new_layers = [tfm.reset_stage_slots(st, ss, is0, mask, ptab_rows,
                                        prefix_len)
                  for st, ss, is0 in zip(cfg.stages, state["layers"],
                                         init_state["layers"])]
    return {"layers": new_layers}


def rollback_paged_slots(cfg: ModelCfg, state, mask, new_len) -> Dict:
    """Speculative rejection: for slots where ``mask`` is set, invalidate
    every written KV row at positions >= ``new_len`` (the slot's next write
    position after accepting the agreeing draft prefix) by resetting its
    ``kpos`` entry to -1 and clamping ``slen``.

    Only per-slot position metadata moves — the K/V pools themselves (and
    int8 scale rows) are untouched, so shared COW prefix pages and their
    scales can never be corrupted by a rejected draft tail: drafts only
    ever write beyond the prompt, into pages the slot privately owns (see
    ``serve.pool``).  ``kpos`` stores absolute positions, so the rejected
    tail is exactly the entries holding a value >= new_len; the stale K/V
    bytes they pointed at stay dead until the next tick's scatter
    overwrites them (writes always precede attention within a tick).

    mask: (B,) bool; new_len: (B,) int32.  One trace per engine — the
    engine jits this donated and dispatches it only on ticks that actually
    rejected drafts."""
    new_layers = [tfm.rollback_stage_slots(st, ss, mask, new_len)
                  for st, ss in zip(cfg.stages, state["layers"])]
    return {"layers": new_layers}


def copy_kv_pages(cfg: ModelCfg, state, src, dst) -> Dict:
    """Copy-on-write support: duplicate pool pages ``src[i] -> dst[i]`` in
    every paged global-attention layer (all layers share one page allocator,
    so a single (src, dst) pair list covers the whole stack).

    Used by the serving engine when a request's prompt diverges from a cached
    prefix mid-page: the matched part of the page is copied into a private
    page the new request owns, then prefill overwrites the divergent tail
    (stale offsets stay masked via kpos until written).  src/dst: (K,) int32;
    padding entries carry src == dst == n_pages and clamp to a harmless
    self-copy no-op (see ``kernels.ops.copy_pages``).
    Windowed circular buffers and recurrent states have no shareable pages
    and pass through untouched."""
    from repro.kernels import ops as kops

    def leaf_copy(path, leaf):
        # int8 pools: a page's scale row travels with its values — a COW'd
        # page dequantized against the wrong scales would corrupt the
        # shared prefix
        return leaf if _paged_leaf_axis(path, leaf) is None else \
            kops.copy_pages(leaf, src, dst)

    return jax.tree_util.tree_map_with_path(leaf_copy, state)


def _paged_leaf_axis(path, leaf) -> Optional[int]:
    """Page axis of a paged-pool leaf (kp/vp values and ks/vs scale rows
    are all head-major 4-D pools, so it is ndim-4 — leading layer dims of
    scanned stages ride along), or ``None`` for per-slot state with no
    shareable pages."""
    name = None
    for p in reversed(path):
        if isinstance(p, jax.tree_util.DictKey):
            name = p.key
            break
    return leaf.ndim - 4 if name in ("kp", "vp", "ks", "vs") else None


def gather_kv_page(cfg: ModelCfg, state, page) -> Dict[str, jax.Array]:
    """Pull one pool page's rows out of every paged leaf — K/V values AND,
    for int8 pools, their per-entry scale rows — as a flat {path: rows}
    dict: the unit the tiered pool DEMOTES to host RAM.  Scale rows travel
    with their page, so a demoted int8 page survives a later promotion
    bit-exact (the cross-tier analogue of COW-carries-scales).

    ``page`` is a traced scalar, so the engine's jit of this traces once;
    the dict keys (stringified tree paths) are static structure that
    ``insert_kv_page`` looks up symmetrically."""
    out: Dict[str, jax.Array] = {}

    def leaf_gather(path, leaf):
        ax = _paged_leaf_axis(path, leaf)
        if ax is not None:
            out["".join(str(p) for p in path)] = \
                jax.lax.dynamic_index_in_dim(leaf, page, axis=ax,
                                             keepdims=False)

    jax.tree_util.tree_map_with_path(leaf_gather, state)
    return out


def insert_kv_page(cfg: ModelCfg, state, page_data, page) -> Dict:
    """Scatter one demoted page's rows (``gather_kv_page`` layout) back
    into every paged leaf at device page ``page`` — the PROMOTION write.
    Non-paged leaves pass through untouched, so the engine jits this with
    the state donated (like the COW copy) and the pools update in place;
    issuing it at admission lets jax's async dispatch overlap the copy with
    the tick's compute, the data dependency through the donated state
    keeping it correct regardless of overlap."""
    def leaf_insert(path, leaf):
        ax = _paged_leaf_axis(path, leaf)
        key = "".join(str(p) for p in path)
        if ax is None or key not in page_data:
            return leaf
        return jax.lax.dynamic_update_index_in_dim(
            leaf, page_data[key].astype(leaf.dtype), page, axis=ax)

    return jax.tree_util.tree_map_with_path(leaf_insert, state)


def prefill(params, cfg: ModelCfg, state, tokens, enc_feats=None) -> Dict:
    """Teacher-forced prompt ingestion: fills every attention cache and rolls
    recurrent states forward. tokens: (B,S)."""
    from repro.models.layers import attention as attn_lib

    dt = jnp.dtype(cfg.dtype)
    x, enc = _embed_inputs(params, cfg, {"tokens": tokens,
                                         "img_feats": enc_feats})
    S = tokens.shape[1]
    positions = jnp.arange(S)
    new_layers = []
    for st, sp, ss in zip(cfg.stages, params["stages"], state["layers"]):
        x, ns = _stage_prefill(sp, cfg, st, x, ss, positions, enc)
        new_layers.append(ns)
    return {"layers": new_layers, "pos": jnp.asarray(S, jnp.int32)}


def _stage_prefill(params, cfg, st, x, states, positions, enc):
    """Runs stage_fwd for hidden states while re-deriving caches layerwise.

    Implemented blockwise (no scan) only for repeats==1 stages; scanned stages
    prefill inside the scan.
    """
    from repro.models.layers import attention as attn_lib
    from repro.models.layers import mamba as mamba_lib
    from repro.models.layers import xlstm as xlstm_lib

    def one_block(bp, blk, x, s):
        h = rmsnorm(bp["mixer_norm"], x, cfg.norm_eps)
        if blk.mixer == "attn":
            m = attn_lib.attention_fwd(bp["mixer"], blk.attn, h,
                                       positions=positions, q_chunk=cfg.attn_q_chunk)
            s = attn_lib.prefill_cache(bp["mixer"], blk.attn, s, h, positions)
        elif blk.mixer == "cross_attn":
            m = attn_lib.attention_fwd(bp["mixer"], blk.attn, h, enc=enc,
                                       q_chunk=cfg.attn_q_chunk)
        elif blk.mixer == "mamba":
            m, s = _roll_recurrent(mamba_lib.mamba_fwd, mamba_lib.mamba_decode,
                                   bp["mixer"], blk.mamba, h, s)
        elif blk.mixer == "mlstm":
            m, s = _roll_recurrent(xlstm_lib.mlstm_fwd, xlstm_lib.mlstm_decode,
                                   bp["mixer"], blk.xlstm, h, s)
        else:
            m, s = _roll_recurrent(xlstm_lib.slstm_fwd, xlstm_lib.slstm_decode,
                                   bp["mixer"], blk.xlstm, h, s)
        x = x + m
        if blk.ffn is not None:
            h2 = rmsnorm(bp["ffn_norm"], x, cfg.norm_eps)
            if blk.ffn == "mlp":
                from repro.models.layers.mlp import mlp_fwd
                x = x + mlp_fwd(bp["ffn"], blk.mlp, h2)
            else:
                from repro.models.layers.moe import moe_fwd
                f, _ = moe_fwd(bp["ffn"], blk.moe, h2)
                x = x + f
        return x, s

    if st.repeats == 1:
        new_states = []
        for i, blk in enumerate(st.pattern):
            x, s = one_block(params[i], blk, x, states[i])
            new_states.append(s)
        return x, new_states

    def body(x, xs):
        gp, gs = xs
        ns = []
        for i, blk in enumerate(st.pattern):
            x, s = one_block(gp[i], blk, x, gs[i])
            ns.append(s)
        return x, tuple(ns)

    x, new_states = jax.lax.scan(body, x, (tuple(params), tuple(states)))
    return x, list(new_states)


def _roll_recurrent(fwd, dec, p, c, h, s):
    """Prefill a recurrent mixer: full-seq output + state from stepping the
    last position (cheap approximation is wrong — we must step the whole
    prompt).  We scan the single-step decode over time for the state while
    using the parallel form for the outputs."""
    m = fwd(p, c, h)

    def step(s, h_t):
        _, s = dec(p, c, h_t[:, None, :], s)
        return s, None

    s, _ = jax.lax.scan(step, s, jnp.moveaxis(h, 1, 0))
    return m, s
