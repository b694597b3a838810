"""Distributed flash-decode: single-token attention over a sharded KV cache.

One of the two decode-attention paths under ``serve/``:

- **This module** — the *sharded lock-step* path used by ``launch.serve``
  dry-runs and the distributed decode shapes: the KV cache's *sequence* dim
  is sharded (normal decode: over 'model'; long-context batch=1: over
  ('data','model')).  Each shard produces the partial online-softmax terms
  (local max, local sum, local weighted values); a pmax + two psums over the
  sequence axes combine them.  The communicated payload per layer is
  O(B·kvH·G·hd) — independent of context length — which is what makes
  32k–512k contexts serveable at all (an all-gathered KV would be GBs per
  layer per step).
- **The paged per-slot path** — ``models.layers.attention
  .ragged_attention_step`` (jnp gather over block tables) and
  ``kernels.flash_attention.ragged_paged_flash`` (Pallas, block table as
  scalar prefetch), driven by ``serve.engine.ServeEngine``.
  Use that for mixed-length continuous batching; use this one when the KV
  of a single sequence outgrows one device.

This module also owns the tensor-parallel entry point of the paged flash
kernel (``tp_ragged_paged_flash``): under an
engine mesh the paged KV pools are sharded over the KV-head axis
(serve_step.STATE_AXES "act_kv_heads"), and since GSPMD cannot partition a
``pallas_call``, the kernels run inside an explicit ``shard_map`` over that
axis — each shard dequantizes and attends over ONLY its head slice of the
pools (block tables, slots, and lengths are replicated control data).
Per-KV-head attention has no cross-shard reduction (softmax normalizes over
the unsharded context axis), so no collective appears here; the single
cross-head contraction lives downstream in the out-projection.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import current_mesh, current_rules, shard_map

NEG_INF = -1e30


def _partial_terms(q, k, v, k_pos, pos, window):
    """q: (B,1,kvH,G,hd); k,v: (B,T,kvH,hd); k_pos: (T,).
    Returns (m (B,kvH,G), l (B,kvH,G), o (B,kvH,G,hd))."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgd,btkd->bkgt", q, k).astype(jnp.float32) * scale
    ok = (k_pos >= 0) & (k_pos <= pos)
    if window is not None:
        ok &= (pos - k_pos) < window
    s = jnp.where(ok[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(ok[None, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p.astype(v.dtype), v).astype(jnp.float32)
    return m, l, o


def sp_flash_decode(q, k, v, k_pos, pos, window: Optional[int] = None):
    """Returns (B, 1, kvH, G, hd).  Falls back to the local computation when
    no mesh / no KV-seq sharding is active (unit tests, single host)."""
    mesh = current_mesh()
    rules = current_rules()
    seq_ax = rules.get("act_kv_seq")
    if mesh is None or not seq_ax:
        m, l, o = _partial_terms(q, k, v, k_pos, pos, window)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out[:, None].astype(q.dtype)  # (B,1,kvH,G,hd)

    seq_ax = (seq_ax,) if isinstance(seq_ax, str) else tuple(seq_ax)
    batch_ax = rules.get("act_kv_batch") or ()
    batch_ax = (batch_ax,) if isinstance(batch_ax, str) else tuple(batch_ax)
    bspec = batch_ax if batch_ax else None

    def local_fn(q, k, v, k_pos, pos):
        m, l, o = _partial_terms(q, k, v, k_pos, pos, window)
        m_g = jax.lax.pmax(m, seq_ax)
        corr = jnp.exp(m - m_g)
        l = jax.lax.psum(l * corr, seq_ax)
        o = jax.lax.psum(o * corr[..., None], seq_ax)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype).reshape(q.shape)

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(bspec), P(bspec, seq_ax), P(bspec, seq_ax), P(seq_ax), P()),
        out_specs=P(bspec),
    )(q, k, v, k_pos, pos)


# ---------------------------------------------------------------------------
# KV-head tensor-parallel paged flash (serving engine mesh= path)


def _head_tp(kvH: int):
    """Resolve the active KV-head shard setup: (mesh, head_axis) when a mesh
    is ambient, the rules map "act_kv_heads" to a mesh axis, and the axis
    size divides ``kvH`` — else (None, None) (run the kernel unsharded)."""
    mesh = current_mesh()
    if mesh is None:
        return None, None
    head_ax = current_rules().get("act_kv_heads")
    if not head_ax:
        return None, None
    axes = (head_ax,) if isinstance(head_ax, str) else tuple(head_ax)
    tp = 1
    for a in axes:
        tp *= mesh.shape[a]
    if tp == 1 or kvH % tp != 0:
        return None, None
    return mesh, head_ax


def tp_ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks=None, vs=None,
                          layer=None):
    """``kernels.ops.ragged_paged_flash`` under the engine mesh: shard_map
    over the KV-head axis of q and of the head-major paged pools stacked
    over layers (values and int8 scales share the spec), read at
    ``layer``; ptab/slot/lens/layer replicate.  Falls back to
    the plain kernel call with no mesh, no "act_kv_heads" rule, or an
    indivisible head count."""
    from repro.kernels import ops as kops

    mesh, h = _head_tp(q.shape[1])
    if mesh is None:
        return kops.ragged_paged_flash(q, kp, vp, ptab, slot, lens,
                                       ks=ks, vs=vs, layer=layer)
    pools = (kp, vp) if ks is None else (kp, vp, ks, vs)
    if layer is None:  # one layer's pools: layer 0 of a 1-layer stack
        pools, layer = tuple(x[None] for x in pools), 0
    heads = P(None, h, None, None)  # axis 1 of q

    def local(q, ptab, slot, lens, layer, *pools):
        return kops.ragged_paged_flash(q, pools[0], pools[1], ptab, slot,
                                       lens, *pools[2:], layer=layer)

    return shard_map(
        local, mesh=mesh,
        in_specs=(heads, P(), P(), P(), P())
        + (P(None, None, h, None, None),) * len(pools),
        out_specs=heads)(q, ptab, slot, lens,
                         jnp.asarray(layer, jnp.int32), *pools)
