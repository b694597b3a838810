"""Block / stage composition with lax.scan over repeated layer groups.

A ``Stage`` with ``repeats > 1`` stacks each pattern-position's params along a
leading "layer" axis and scans, keeping HLO size O(|pattern|) regardless of
depth — required for compiling 72-layer models for 512 fake devices on CPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import BlockCfg, ModelCfg, Stage
from repro.parallel.sharding import constrain_like_params
from repro.models.layers import attention as attn
from repro.models.layers import mamba as mamba_lib
from repro.models.layers import xlstm as xlstm_lib
from repro.models.layers.mlp import init_mlp, mlp_fwd
from repro.models.layers.moe import init_moe, moe_fwd
from repro.models.layers.norms import init_rmsnorm, rmsnorm

ZERO_AUX = {"moe_lb_loss": jnp.zeros((), jnp.float32),
            "moe_z_loss": jnp.zeros((), jnp.float32)}


def _add_aux(a, b):
    return {k: a[k] + b[k] for k in a}


# ---------------------------------------------------------------------------
# Single block


def init_block(key, cfg: ModelCfg, blk: BlockCfg):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {"mixer_norm": init_rmsnorm(d)}
    if blk.mixer in ("attn", "cross_attn"):
        p["mixer"] = attn.init_attention(ks[0], d, blk.attn)
    elif blk.mixer == "mamba":
        p["mixer"] = mamba_lib.init_mamba(ks[0], d, blk.mamba)
    elif blk.mixer == "mlstm":
        p["mixer"] = xlstm_lib.init_mlstm(ks[0], d, blk.xlstm)
    elif blk.mixer == "slstm":
        p["mixer"] = xlstm_lib.init_slstm(ks[0], d, blk.xlstm)
    else:
        raise ValueError(f"unknown mixer {blk.mixer}")
    if blk.ffn == "mlp":
        p["ffn_norm"] = init_rmsnorm(d)
        p["ffn"] = init_mlp(ks[1], d, blk.mlp)
    elif blk.ffn == "moe":
        p["ffn_norm"] = init_rmsnorm(d)
        p["ffn"] = init_moe(ks[1], d, blk.moe)
    return p


def block_fwd(params, cfg: ModelCfg, blk: BlockCfg, x, *, positions=None, enc=None):
    """Returns (x, aux) — aux always has ZERO_AUX structure (scan-uniform)."""
    aux = dict(ZERO_AUX)
    h = rmsnorm(params["mixer_norm"], x, cfg.norm_eps)
    if blk.mixer == "attn":
        m = attn.attention_fwd(params["mixer"], blk.attn, h, positions=positions,
                               q_chunk=cfg.attn_q_chunk, use_flash=cfg.use_flash)
    elif blk.mixer == "cross_attn":
        m = attn.attention_fwd(params["mixer"], blk.attn, h, enc=enc,
                               q_chunk=cfg.attn_q_chunk)
    elif blk.mixer == "mamba":
        m = mamba_lib.mamba_fwd(params["mixer"], blk.mamba, h)
    elif blk.mixer == "mlstm":
        m = xlstm_lib.mlstm_fwd(params["mixer"], blk.xlstm, h)
    else:
        m = xlstm_lib.slstm_fwd(params["mixer"], blk.xlstm, h)
    x = x + m
    if blk.ffn is not None:
        h = rmsnorm(params["ffn_norm"], x, cfg.norm_eps)
        if blk.ffn == "mlp":
            f = mlp_fwd(params["ffn"], blk.mlp, h)
        else:
            f, moe_aux = moe_fwd(params["ffn"], blk.moe, h)
            aux = _add_aux(aux, moe_aux)
        x = x + f
    return x, aux


# ---------------------------------------------------------------------------
# Stages (scan over repeats)


def init_stage(key, cfg: ModelCfg, stage: Stage):
    """Per-block params, stacked over ``stage.repeats``.  Block ``i`` of
    repeat ``r`` draws from ``fold_in(fold_in(key, r), i)``; the repeats are
    drawn under ``vmap`` over their keys (bit-identical to a loop), so a
    jitted init holds one copy of each block's random ops instead of one
    per layer — a published-width init then compiles in seconds."""
    if stage.repeats == 1:
        kr = jax.random.fold_in(key, 0)
        return [init_block(jax.random.fold_in(kr, i), cfg, blk)
                for i, blk in enumerate(stage.pattern)]
    keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(
        jnp.arange(stage.repeats))
    return [jax.vmap(lambda kr, i=i, blk=blk: init_block(
                jax.random.fold_in(kr, i), cfg, blk))(keys)
            for i, blk in enumerate(stage.pattern)]


@jax.custom_jvp
def _barrier(xs):
    """``lax.optimization_barrier`` with a differentiation rule.

    The barrier serializes FSDP param gathers block-by-block (see
    ``stage_fwd.group``), but jax defines no JVP for the primitive, which
    made every remat'd-scan train step non-differentiable (the seed-era
    xfail group).  The barrier is semantically the identity, so the custom
    JVP keeps the scheduling fence on the PRIMAL path and passes tangents
    straight through; the tangent map is the identity, so transposition
    (grad) is exact and the fence never constrains the backward schedule —
    the xs-grad accumulators already serialize along the scan carry."""
    return jax.lax.optimization_barrier(xs)


@_barrier.defjvp
def _barrier_jvp(primals, tangents):
    (xs,), (dxs,) = primals, tangents
    return _barrier(xs), dxs


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol, prevent_cse=False)


def stage_fwd(params, cfg: ModelCfg, stage: Stage, x, *, positions=None, enc=None):
    from repro.parallel.sharding import lshard

    def one_block(block_params, blk, x):
        x, a = block_fwd(block_params, cfg, blk, x, positions=positions, enc=enc)
        if (cfg.remat != "none" and cfg.seq_shard_residuals
                and x.shape[1] > 1):
            # seq-shard the saved boundary over 'model' (Megatron-SP style):
            # stored residuals must not be replicated across the TP axis
            x = lshard(x, "act_batch", "act_res_seq", None)
        return x, a

    def group(x, group_params):
        # Constrain params at USE, inside the scan body: GSPMD does not
        # propagate outer constraints into while-loop bodies, so without
        # this both the per-layer param gathers (forward) and the xs-grad
        # accumulators (backward) end up replicated across mesh axes
        # (measured: +100 GiB/dev on jamba-398b).
        group_params = constrain_like_params(group_params)
        # nested remat: the group is checkpointed (scan stores only group
        # boundaries) and each block inside is checkpointed again (the
        # recomputed forward stores only block boundaries; block internals
        # are rematerialized one block at a time during backward)
        aux = dict(ZERO_AUX)
        for i, blk in enumerate(stage.pattern):
            if i > 0:
                # serialize FSDP param gathers block-by-block: without the
                # barrier the scheduler gathers the whole group's params up
                # front (~10 GiB/dev live at jamba scale)
                x, p_i = _barrier((x, group_params[i]))
            else:
                p_i = group_params[i]
            blk_fn = _remat(lambda p, y, b=blk: one_block(p, b, y), cfg.remat)
            x, a = blk_fn(p_i, x)
            aux = _add_aux(aux, a)
        return x, aux

    group = _remat(group, cfg.remat)

    if stage.repeats == 1:
        return group(x, params)

    def body(carry, group_params):
        x, aux = carry
        x, a = group(x, group_params)
        return (x, _add_aux(aux, a)), None

    (x, aux), _ = jax.lax.scan(body, (x, dict(ZERO_AUX)), tuple(params))
    return x, aux


# ---------------------------------------------------------------------------
# Decode (single-token step with per-layer cache/state)


def init_block_state(params, cfg: ModelCfg, blk: BlockCfg, batch: int,
                     cache_len: int, dtype, enc=None):
    if blk.mixer == "attn":
        return attn.init_cache(blk.attn, batch, cache_len, dtype)
    if blk.mixer == "cross_attn":
        return attn.init_cross_cache(params["mixer"], blk.attn, enc)
    if blk.mixer == "mamba":
        return mamba_lib.init_mamba_state(blk.mamba, cfg.d_model, batch, dtype)
    if blk.mixer == "mlstm":
        return xlstm_lib.init_mlstm_state(blk.xlstm, cfg.d_model, batch, dtype)
    return xlstm_lib.init_slstm_state(blk.xlstm, cfg.d_model, batch, dtype)


def block_decode(params, cfg: ModelCfg, blk: BlockCfg, x, state, *,
                 sp_decode: bool = False):
    h = rmsnorm(params["mixer_norm"], x, cfg.norm_eps)
    if blk.mixer in ("attn", "cross_attn"):
        m, state = attn.attention_decode(params["mixer"], blk.attn, h, state,
                                         sp_decode=sp_decode and blk.mixer == "attn")
    elif blk.mixer == "mamba":
        m, state = mamba_lib.mamba_decode(params["mixer"], blk.mamba, h, state)
    elif blk.mixer == "mlstm":
        m, state = xlstm_lib.mlstm_decode(params["mixer"], blk.xlstm, h, state)
    else:
        m, state = xlstm_lib.slstm_decode(params["mixer"], blk.xlstm, h, state)
    x = x + m
    if blk.ffn is not None:
        h = rmsnorm(params["ffn_norm"], x, cfg.norm_eps)
        if blk.ffn == "mlp":
            f = mlp_fwd(params["ffn"], blk.mlp, h)
        else:
            f, _ = moe_fwd(params["ffn"], blk.moe, h)
        x = x + f
    return x, state


def init_stage_state(params, cfg: ModelCfg, stage: Stage, batch: int,
                     cache_len: int, dtype, enc=None):
    if stage.repeats == 1:
        return [init_block_state(params[i], cfg, blk, batch, cache_len, dtype, enc)
                for i, blk in enumerate(stage.pattern)]
    out = []
    for i, blk in enumerate(stage.pattern):
        if blk.mixer == "cross_attn":
            # enc projections differ per repeat: vmap over stacked params
            out.append(jax.vmap(
                lambda p: attn.init_cross_cache(p["mixer"], blk.attn, enc))(params[i]))
            continue
        one_params = jax.tree.map(lambda x: x[0], params[i])
        one = init_block_state(one_params, cfg, blk, batch, cache_len, dtype, enc)
        out.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (stage.repeats,) + x.shape).copy(), one))
    return out


# ---------------------------------------------------------------------------
# Paged serving step (per-slot positions; C >= 1 tokens per slot per call)


def init_block_state_paged(params, cfg: ModelCfg, blk: BlockCfg, batch: int,
                           cache_len: int, dtype, *, page_size: int,
                           n_pages: int, window_extra: int = 0,
                           kv_dtype=None):
    if blk.mixer == "attn":
        return attn.init_paged_cache(blk.attn, batch, cache_len, dtype,
                                     page_size=page_size, n_pages=n_pages,
                                     window_extra=window_extra,
                                     kv_dtype=kv_dtype)
    if blk.mixer == "cross_attn":
        raise NotImplementedError("paged serving covers token models only")
    if blk.mixer == "mamba":
        return mamba_lib.init_mamba_state(blk.mamba, cfg.d_model, batch, dtype)
    if blk.mixer == "mlstm":
        return xlstm_lib.init_mlstm_state(blk.xlstm, cfg.d_model, batch, dtype)
    return xlstm_lib.init_slstm_state(blk.xlstm, cfg.d_model, batch, dtype)


def init_stage_state_paged(params, cfg: ModelCfg, stage: Stage, batch: int,
                           cache_len: int, dtype, *, page_size: int,
                           n_pages: int, window_extra: int = 0,
                           kv_dtype=None):
    mk = lambda: [init_block_state_paged(None, cfg, blk, batch, cache_len,
                                         dtype, page_size=page_size,
                                         n_pages=n_pages,
                                         window_extra=window_extra,
                                         kv_dtype=kv_dtype)
                  for blk in stage.pattern]
    if stage.repeats == 1:
        return mk()
    one = mk()
    return [jax.tree.map(
        lambda x: jnp.broadcast_to(x, (stage.repeats,) + x.shape).copy(), s)
        for s in one]


def _ragged_recurrent_roll(dec, p, c, h, s, slot, seq_idx, valid, width: int):
    """Ragged pack -> per-slot dense -> masked roll -> scatter back.

    Recurrent mixers must consume a slot's tokens in position order, but the
    pack interleaves slots.  The scheduler guarantees (a) at most ``width``
    tokens per slot per pack and (b) in-pack position order, so a scatter by
    (slot, intra-slot ordinal) into a dense (B, width) layout lets a scan of
    the single-step decode run over the width columns, advancing state only
    where valid — pad tails and idle slots keep their state bit-identical;
    outputs gather back by the same indices.  h: (1,T,D); slot/seq_idx/
    valid: (T,).
    """
    B = next(iter(jax.tree.leaves(s))).shape[0]
    h0 = h[0]  # (T,D)
    col = jnp.where(valid, seq_idx, width)
    dense = jnp.zeros((B, width, h0.shape[-1]), h0.dtype)
    dense = dense.at[slot, col].set(h0, mode="drop")
    vdense = jnp.zeros((B, width), bool).at[slot, col].set(valid, mode="drop")

    def step(s, inp):
        h_t, v_t = inp
        y, s_new = dec(p, c, h_t[:, None, :], s)
        s = jax.tree.map(
            lambda a, b: jnp.where(
                v_t.reshape((-1,) + (1,) * (a.ndim - 1)), a, b), s_new, s)
        return s, y[:, 0]

    s, ys = jax.lax.scan(
        step, s, (jnp.moveaxis(dense, 1, 0), jnp.moveaxis(vdense, 1, 0)))
    y_dense = jnp.moveaxis(ys, 0, 1)
    y = y_dense[slot, jnp.minimum(col, width - 1)]  # (T,D); invalid rows junk
    return y[None], s


def block_step_ragged(params, cfg: ModelCfg, blk: BlockCfg, x, state, slot,
                      q_pos, seq_idx, valid, *, width: int,
                      flash_decode: bool = False, layer=None):
    h = rmsnorm(params["mixer_norm"], x, cfg.norm_eps)
    if blk.mixer == "attn":
        m, state = attn.ragged_attention_step(params["mixer"], blk.attn, h,
                                              state, slot, q_pos, valid,
                                              flash_decode=flash_decode,
                                              layer=layer)
    elif blk.mixer == "mamba":
        m, state = _ragged_recurrent_roll(
            mamba_lib.mamba_decode, params["mixer"], blk.mamba, h, state,
            slot, seq_idx, valid, width)
    elif blk.mixer == "mlstm":
        m, state = _ragged_recurrent_roll(
            xlstm_lib.mlstm_decode, params["mixer"], blk.xlstm, h, state,
            slot, seq_idx, valid, width)
    elif blk.mixer == "slstm":
        m, state = _ragged_recurrent_roll(
            xlstm_lib.slstm_decode, params["mixer"], blk.xlstm, h, state,
            slot, seq_idx, valid, width)
    else:
        raise NotImplementedError(f"ragged serving: unsupported mixer {blk.mixer}")
    x = x + m
    if blk.ffn is not None:
        h = rmsnorm(params["ffn_norm"], x, cfg.norm_eps)
        if blk.ffn == "mlp":
            f = mlp_fwd(params["ffn"], blk.mlp, h)
        else:
            f, _ = moe_fwd(params["ffn"], blk.moe, h)
        x = x + f
    return x, state


def stage_step_ragged(params, cfg: ModelCfg, stage: Stage, x, states, slot,
                      q_pos, seq_idx, valid, *, width: int,
                      flash_decode: bool = False):
    if stage.repeats == 1:
        new_states = []
        for i, blk in enumerate(stage.pattern):
            x, s = block_step_ragged(params[i], cfg, blk, x, states[i], slot,
                                     q_pos, seq_idx, valid, width=width,
                                     flash_decode=flash_decode)
            new_states.append(s)
        return x, new_states

    # The shared pool leaves stay stacked over the stage's layers and ride
    # in the scan's carry; each layer writes and reads them at its own index.
    # Scanned as xs and re-stacked as ys instead, every layer's pools would
    # be sliced out and copied back whole on every tick.  Per-slot leaves
    # and recurrent or windowed state are scanned as before.
    pools = tuple({n: v for n, v in s.items() if n in attn.POOL_LEAVES}
                  for s in states)
    rest = tuple({n: v for n, v in s.items() if n not in attn.POOL_LEAVES}
                 for s in states)

    def body(carry, xs):
        x, pools = carry
        group_params, group_rest, layer = xs
        new_pools, new_rest = [], []
        for i, blk in enumerate(stage.pattern):
            x, s = block_step_ragged(group_params[i], cfg, blk, x,
                                     {**group_rest[i], **pools[i]}, slot,
                                     q_pos, seq_idx, valid, width=width,
                                     flash_decode=flash_decode, layer=layer)
            new_pools.append({n: s[n] for n in pools[i]})
            new_rest.append({n: s[n] for n in group_rest[i]})
        return (x, tuple(new_pools)), tuple(new_rest)

    (x, pools), rest = jax.lax.scan(
        body, (x, pools),
        (tuple(params), rest, jnp.arange(stage.repeats, dtype=jnp.int32)))
    return x, [{**r, **p} for r, p in zip(rest, pools)]


def reset_stage_slots(stage: Stage, states, init_states, mask, ptab_rows,
                      prefix_len):
    """Reset per-slot rows (admission): install ``ptab_rows`` into block
    tables, restore every other per-row leaf from the fresh-init template.
    KV pools are shared across slots and left alone — stale pages are dead
    via kpos/ptab, and pages owned by the prefix cache must survive slot
    churn.  ``prefix_len`` (B,) is the number of leading tokens whose KV the
    slot inherits from shared prefix pages already present in the pool: those
    positions get live ``kpos`` (0..prefix_len-1 at their natural cache
    index, which for paged layers is the absolute position) and ``slen``
    starts at ``prefix_len``, so attention sees the reused prefix without a
    single prefill token being recomputed.  A zero prefix_len reproduces the
    old cold-slot reset exactly.  mask: (B,), ptab_rows: (B, pages_per_slot),
    prefix_len: (B,) int32."""
    lead = 1 if stage.repeats > 1 else 0
    out = []
    for s_blk, i_blk in zip(states, init_states):
        new = {}
        for name, leaf in s_blk.items():
            # shared pool leaves survive slot churn: KV pages AND their
            # int8 scale rows (a reset must never zero scales a prefix-
            # cached page still dequantizes against)
            if name in attn.POOL_LEAVES:
                new[name] = leaf
                continue
            m = mask.reshape((1,) * lead + (-1,) + (1,) * (leaf.ndim - lead - 1))
            if name == "kpos":
                iota = jnp.arange(leaf.shape[-1], dtype=jnp.int32)[None, :]
                src = jnp.where(iota < prefix_len[:, None], iota, -1)
            elif name == "slen":
                src = prefix_len.astype(leaf.dtype)
            elif name == "ptab":
                src = ptab_rows
            else:
                src = i_blk[name]
            new[name] = jnp.where(m, src, leaf)
        out.append(new)
    return out


def rollback_stage_slots(stage: Stage, states, mask, new_len):
    """Speculative rejection: for masked slots, kill the position metadata
    of every KV row written past ``new_len`` — ``kpos`` entries holding a
    value >= new_len drop to -1 (unwritten) and ``slen`` clamps down.  KV
    pools, scale pools, block tables and recurrent/windowed state all pass
    through untouched: rejected draft bytes stay in their pages, dead via
    kpos, until the next tick's scatter overwrites them.  The value-based
    test (rather than cache-index iota) works because ``kpos`` stores
    absolute positions wherever they land.  mask: (B,), new_len: (B,)."""
    lead = 1 if stage.repeats > 1 else 0
    out = []
    for s_blk in states:
        new = {}
        for name, leaf in s_blk.items():
            if name == "kpos":
                m = mask.reshape((1,) * lead + (-1,) + (1,) * (leaf.ndim - lead - 1))
                nl = new_len[:, None]
                new[name] = jnp.where(m & (leaf >= nl), -1, leaf)
            elif name == "slen":
                m = mask.reshape((1,) * lead + (-1,) + (1,) * (leaf.ndim - lead - 1))
                nl = new_len.astype(leaf.dtype)
                new[name] = jnp.where(m, jnp.minimum(leaf, nl), leaf)
            else:
                new[name] = leaf
        out.append(new)
    return out


def stage_decode(params, cfg: ModelCfg, stage: Stage, x, states, *,
                 sp_decode: bool = False):
    if stage.repeats == 1:
        new_states = []
        for i, blk in enumerate(stage.pattern):
            x, s = block_decode(params[i], cfg, blk, x, states[i], sp_decode=sp_decode)
            new_states.append(s)
        return x, new_states

    def body(x, xs):
        group_params, group_states = xs
        new_states = []
        for i, blk in enumerate(stage.pattern):
            x, s = block_decode(group_params[i], cfg, blk, x, group_states[i],
                                sp_decode=sp_decode)
            new_states.append(s)
        return x, tuple(new_states)

    x, new_states = jax.lax.scan(body, x, (tuple(params), tuple(states)))
    return x, list(new_states)
