"""Layer-level invariants: RoPE, attention variants, mixers, MoE."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.base import AttnCfg, MambaCfg, MoECfg, XLSTMCfg
from repro.models.layers import attention as A
from repro.models.layers import mamba as Mb
from repro.models.layers import xlstm as X
from repro.models.layers.conv import causal_depthwise_conv, conv_step
from repro.models.layers.embeddings import apply_rope
from repro.models.layers.moe import init_moe, moe_fwd

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# RoPE


def test_rope_preserves_norm():
    x = jax.random.normal(KEY, (2, 16, 3, 4, 32), jnp.float32)
    y = apply_rope(x, jnp.arange(16), 1e4)
    np.testing.assert_allclose(jnp.linalg.norm(x, axis=-1),
                               jnp.linalg.norm(y, axis=-1), rtol=1e-5)


def test_rope_relative_property():
    """<rope(q,m), rope(k,n)> depends only on m - n."""
    q = jax.random.normal(KEY, (1, 1, 1, 1, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 1, 32), jnp.float32)

    def dot_at(m, n):
        qm = apply_rope(jnp.broadcast_to(q, (1, 1, 1, 1, 32)), jnp.array([m]), 1e4)
        kn = apply_rope(jnp.broadcast_to(k, (1, 1, 1, 1, 32)), jnp.array([n]), 1e4)
        return float(jnp.sum(qm * kn))

    assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4
    assert abs(dot_at(7, 0) - dot_at(17, 10)) < 1e-4


# ---------------------------------------------------------------------------
# Attention


def _mk_attn(num_heads=4, num_kv=2, hd=16, **kw):
    cfg = AttnCfg(num_heads=num_heads, num_kv_heads=num_kv, head_dim=hd,
                  rope_theta=1e4, **kw)
    params = A.init_attention(KEY, 32, cfg)
    return cfg, params


def test_gqa_equals_repeated_mha():
    """GQA with kv heads broadcast == MHA with physically repeated KV."""
    x = jax.random.normal(KEY, (2, 24, 32), jnp.float32)
    cfg_g, p_g = _mk_attn(num_heads=4, num_kv=2)
    cfg_m = AttnCfg(num_heads=4, num_kv_heads=4, head_dim=16, rope_theta=1e4)
    p_m = dict(p_g)
    # physically repeat KV heads: (D, 2, hd) -> (D, 4, hd); regroup q/wo
    p_m["wk"] = jnp.repeat(p_g["wk"], 2, axis=1)
    p_m["wv"] = jnp.repeat(p_g["wv"], 2, axis=1)
    p_m["wq"] = p_g["wq"].reshape(32, 4, 1, 16)  # (D,kvH=4,G=1,hd)
    p_m["wo"] = p_g["wo"].reshape(4, 1, 16, 32)
    y_g = A.attention_fwd(p_g, cfg_g, x)
    y_m = A.attention_fwd(p_m, cfg_m, x)
    np.testing.assert_allclose(y_g, y_m, rtol=1e-4, atol=1e-5)


def test_chunked_equals_full():
    x = jax.random.normal(KEY, (2, 64, 32), jnp.float32)
    cfg, p = _mk_attn()
    y_full = A.attention_fwd(p, cfg, x, q_chunk=64)  # full path (S <= 2*chunk)
    y_chunk = A.attention_fwd(p, cfg, x, q_chunk=16)
    np.testing.assert_allclose(y_full, y_chunk, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window,chunk", [(None, 6), (8, 6), (8, 24), (8, 1)])
def test_paged_chunked_prefill_matches_fwd_oracle(window, chunk):
    """Numerical oracle for the serving prefill path: feeding a prompt
    through ragged_attention_step in C-token chunks (each tick one pack of
    every slot's next chunk) must reproduce attention_fwd's full-sequence
    outputs at every position — including windowed layers where a chunk
    write evicts circular-buffer entries (window_extra = C-1 keeps every
    in-window key resident)."""
    cfg, p = _mk_attn(window=window)
    B, S = 2, 24
    x = jax.random.normal(KEY, (B, S, 32), jnp.float32)
    want = A.attention_fwd(p, cfg, x, positions=jnp.arange(S), q_chunk=128)
    cache = A.init_paged_cache(cfg, B, 32, jnp.float32, page_size=4,
                               n_pages=2 * 8, window_extra=chunk - 1)
    if "ptab" in cache:  # map pages: slot b owns pool rows [8b, 8(b+1))
        cache["ptab"] = jnp.asarray([[8 * b + i for i in range(8)]
                                     for b in range(B)], jnp.int32)
    outs = []
    for c0 in range(0, S, chunk):
        C = min(chunk, S - c0)
        slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), C)
        q_pos = jnp.tile(c0 + jnp.arange(C, dtype=jnp.int32), B)
        o, cache = A.ragged_attention_step(
            p, cfg, x[:, c0:c0 + C].reshape(1, B * C, -1), cache, slot,
            q_pos, jnp.ones((B * C,), bool))
        outs.append(o.reshape(B, C, -1))
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_sliding_window_masks_far_past():
    """With window w, output at t ignores inputs older than t - w + 1."""
    cfg, p = _mk_attn(window=8)
    x = jax.random.normal(KEY, (1, 32, 32), jnp.float32)
    x2 = x.at[:, :16].set(jax.random.normal(jax.random.PRNGKey(9), (1, 16, 32)))
    y1 = A.attention_fwd(p, cfg, x)
    y2 = A.attention_fwd(p, cfg, x2)
    np.testing.assert_allclose(y1[:, 24:], y2[:, 24:], rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(y1[:, :16] - y2[:, :16]))) > 1e-3


def test_decode_matches_fwd():
    cfg, p = _mk_attn()
    x = jax.random.normal(KEY, (2, 17, 32), jnp.float32)
    y = A.attention_fwd(p, cfg, x)
    cache = A.init_cache(cfg, 2, 32, jnp.float32)
    cache = A.prefill_cache(p, cfg, cache, x[:, :-1], jnp.arange(16))
    y_t, _ = A.attention_decode(p, cfg, x[:, -1:], cache)
    np.testing.assert_allclose(y_t[:, 0], y[:, -1], rtol=1e-4, atol=1e-5)


def test_windowed_circular_cache_decode():
    """Windowed decode with a circular window-sized cache matches full fwd."""
    cfg, p = _mk_attn(window=8)
    S = 24
    x = jax.random.normal(KEY, (1, S, 32), jnp.float32)
    y = A.attention_fwd(p, cfg, x)
    cache = A.init_cache(cfg, 1, 512, jnp.float32)
    assert cache["k"].shape[1] == 8  # capacity = window
    outs = []
    for t in range(S):
        y_t, cache = A.attention_decode(p, cfg, x[:, t : t + 1], cache)
        outs.append(y_t[:, 0])
    np.testing.assert_allclose(jnp.stack(outs, 1), y, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Recurrent mixers


def test_mlstm_chunkwise_matches_sequential():
    cfg = XLSTMCfg(kind="mlstm", num_heads=4, proj_factor=2.0)
    p = X.init_mlstm(KEY, 64, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 64), jnp.float32)
    np.testing.assert_allclose(X.mlstm_fwd(p, cfg, x, chunk=16),
                               X.mlstm_fwd_seq(p, cfg, x), rtol=1e-4, atol=1e-5)


def test_mlstm_decode_matches_fwd():
    cfg = XLSTMCfg(kind="mlstm", num_heads=2, proj_factor=2.0)
    p = X.init_mlstm(KEY, 32, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 32), jnp.float32)
    y = X.mlstm_fwd_seq(p, cfg, x)
    st_ = X.init_mlstm_state(cfg, 32, 2, jnp.float32)
    for t in range(20):
        y_t, st_ = X.mlstm_decode(p, cfg, x[:, t : t + 1], st_)
    np.testing.assert_allclose(y_t[:, 0], y[:, -1], rtol=1e-4, atol=1e-5)


def test_mamba_chunk_invariance_and_decode():
    cfg = MambaCfg(d_state=4)
    p = Mb.init_mamba(KEY, 32, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 32), jnp.float32)
    y1 = Mb.mamba_fwd(p, cfg, x, chunk=48)
    y2 = Mb.mamba_fwd(p, cfg, x, chunk=8)
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-6)
    st_ = Mb.init_mamba_state(cfg, 32, 2, jnp.float32)
    for t in range(48):
        y_t, st_ = Mb.mamba_decode(p, cfg, x[:, t : t + 1], st_)
    np.testing.assert_allclose(y_t[:, 0], y1[:, -1], rtol=1e-4, atol=1e-5)


def test_slstm_decode_matches_fwd():
    cfg = XLSTMCfg(kind="slstm", num_heads=2)
    p = X.init_slstm(KEY, 32, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32), jnp.float32)
    y = X.slstm_fwd(p, cfg, x, chunk=8)
    st_ = X.init_slstm_state(cfg, 32, 2, jnp.float32)
    for t in range(24):
        y_t, st_ = X.slstm_decode(p, cfg, x[:, t : t + 1], st_)
    np.testing.assert_allclose(y_t[:, 0], y[:, -1], rtol=1e-4, atol=1e-5)


def test_causal_conv_step_consistency():
    w = jax.random.normal(KEY, (4, 8), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (8,), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 8), jnp.float32)
    y = causal_depthwise_conv(x, w, b)
    state = jnp.zeros((2, 3, 8))
    outs = []
    for t in range(12):
        o, state = conv_step(x[:, t], state, w, b)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), y, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# MoE


def test_moe_dispatch_matches_ragged_at_high_capacity():
    cfg_d = MoECfg(num_experts=4, top_k=2, d_ff=32, capacity_factor=64.0)
    cfg_r = dataclasses.replace(cfg_d, impl="ragged", capacity_factor=1.25)
    p = init_moe(KEY, 16, cfg_d)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16), jnp.float32)
    y_d, aux_d = moe_fwd(p, cfg_d, x)
    y_r, aux_r = moe_fwd(p, cfg_r, x)
    np.testing.assert_allclose(y_d, y_r, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(aux_d["moe_lb_loss"], aux_r["moe_lb_loss"], rtol=1e-5)


def test_moe_capacity_drops_tokens():
    """At cf=0.25 some tokens must be dropped -> outputs differ from dropless."""
    cfg_low = MoECfg(num_experts=4, top_k=1, d_ff=32, capacity_factor=0.25)
    cfg_r = dataclasses.replace(cfg_low, impl="ragged")
    p = init_moe(KEY, 16, cfg_low)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 16), jnp.float32)
    y_low, _ = moe_fwd(p, cfg_low, x)
    y_free, _ = moe_fwd(p, cfg_r, x)
    assert float(jnp.max(jnp.abs(y_low - y_free))) > 1e-4


def test_moe_dense_residual():
    from repro.configs.base import MLPCfg

    cfg = MoECfg(num_experts=4, top_k=1, d_ff=32,
                 dense_residual=MLPCfg(d_ff=32))
    p = init_moe(KEY, 16, cfg)
    assert "dense" in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)
    y, _ = moe_fwd(p, cfg, x)
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))


@settings(max_examples=6, deadline=None)
@given(e=st.sampled_from([2, 4, 8]), k=st.integers(2, 2))
def test_moe_gates_convexity(e, k):
    """Ragged MoE output is a convex combination of per-expert outputs:
    with identical experts and k>=2 (renormalized gates sum to 1) the MoE
    equals the single-expert MLP.  (top-1 keeps the raw softmax gate by
    design — Switch-style — so it scales the output instead.)"""
    cfg = MoECfg(num_experts=e, top_k=k, d_ff=16, impl="ragged")
    p = init_moe(KEY, 8, cfg)
    p = dict(p)
    for nm in ("we_gate", "we_up", "we_down"):
        p[nm] = jnp.broadcast_to(p[nm][:1], p[nm].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8), jnp.float32)
    y, _ = moe_fwd(p, cfg, x)
    from repro.configs.base import MLPCfg
    from repro.models.layers.mlp import mlp_fwd

    ref_ = mlp_fwd({"w_gate": p["we_gate"][0], "w_up": p["we_up"][0],
                    "w_down": p["we_down"][0]}, MLPCfg(d_ff=16), x)
    np.testing.assert_allclose(y, ref_, rtol=1e-4, atol=1e-5)
