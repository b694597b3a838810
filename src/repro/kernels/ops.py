"""Jit'd public wrappers around the Pallas kernels.

Every Pallas entry point resolves its ``interpret`` flag through
``default_interpret()``: interpret mode off-TPU, compiled for real on a TPU
backend.

This module also owns the serving cache's int8 machinery: the paged KV pool
can store K/V pages as symmetric int8 with one float32 scale per pool entry
per KV head (``quantize_kv`` / ``dequantize_kv``, absmax over the head dim),
written by the serving steps' quantize-on-write KV scatter
(``models.layers.attention``) and read back by the fused-dequant path of
``ragged_paged_flash`` (scales applied in VMEM right after the page DMA).
``copy_pages`` carries the scale rows along with their pages so
copy-on-write stays correct for quantized pools.

Paged pools are HEAD-MAJOR (see ``kernels.flash_attention``): values
``(n_pages, kvH, page, hd)``, int8 scales ``(n_pages, kvH, 1, page)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import rmsnorm as _rn
from repro.kernels._interpret import default_interpret  # noqa: F401 (public)


def matmul(a, b, *, block=(256, 256, 256), accum="vmem", out_dtype=None):
    return _mm.matmul(a, b, block=block, accum=accum,
                      interpret=default_interpret(), out_dtype=out_dtype)


def flash_attention(q, k, v, *, bq=128, bk=128, window=None):
    return _fa.flash_attention(q, k, v, bq=bq, bk=bk, window=window,
                               interpret=default_interpret())


def ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks=None, vs=None, *,
                       layer=None):
    """Ragged-pack serving attention over a block-table-paged KV pool.
    q: (T,kvH,G,hd); slot/lens: (T,); kp/vp: (n_pages,kvH,page,hd), or
    (L,n_pages,kvH,page,hd) stacked over a scanned stage with ``layer``
    the scalar index of the plane to read -> (T,kvH,G,hd).

    Prefix-shared pages need no kernel support: the kernel resolves
    run -> slot -> page through ``ptab`` for every page it fetches, so two
    slots whose block-table rows point at the same pool page simply DMA the
    same bytes — sharing and copy-on-write are entirely a host-side
    allocator concern.  int8 pools pass scale pools ``ks``/``vs``
    ((n_pages,kvH,1,page) f32); dequant is fused into the kernel's inner
    loop, so the HBM traffic per page is the int8 bytes plus its scales —
    not a dequantized copy."""
    return _fa.ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks=ks, vs=vs,
                                  layer=layer, interpret=default_interpret())


# ---------------------------------------------------------------------------
# int8 KV quantization (paged serving pools)


def quantize_kv(x):
    """Symmetric int8 quantization of KV rows: one scale per (.., kvH) row.

    x: (..., kvH, hd) float rows -> (int8 rows, float32 scales (..., kvH)).
    scale = absmax(|row|)/127 over the head dim (clamped away from zero so
    all-zero rows round-trip to zeros), values round-to-nearest into
    [-127, 127].  The worst-case per-element reconstruction error is
    scale/2 = absmax/254.
    """
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127.0, 127.0).astype(jnp.int8)
    return q, s


def dequantize_kv(q, s, dtype=jnp.float32):
    """Inverse of ``quantize_kv``: q (..., kvH, hd) int8, s (..., kvH)."""
    return (q.astype(jnp.float32) * s[..., None].astype(jnp.float32)).astype(dtype)


def copy_pages(pool, src, dst):
    """Copy-on-write page copy: ``pool[..., dst[i], :, :, :] = pool[..., src[i], :, :, :]``.

    pool: (..., n_pages, kvH, page, hd) KV pool or (..., n_pages, kvH, 1,
    page) scale pool — the page axis is ``ndim - 4`` for both, and an
    optional leading layer axis from scanned stages rides along in each
    slice.  Scale rows MUST travel with their pages — a COW'd int8 page
    dequantized against another page's scales would silently corrupt the
    copied prefix.  src/dst: (K,) int32 with a FIXED K (the engine pads
    unused pairs with the ``n_pages`` sentinel), so the op stays one traced
    program.  Implemented as K unrolled dynamic-slice updates rather than
    one batched scatter: with the pool donated, each update is an in-place
    page-sized memcpy (the same pattern as a KV-cache write), whereas a
    scatter with leading batch-dim slices makes XLA CPU rewrite the whole
    pool (~2 model steps per call when measured).  Sentinel pairs clamp to
    a self-copy of the last page — a byte-identical no-op."""
    ax = pool.ndim - 4
    n = pool.shape[ax]
    for i in range(src.shape[0]):
        v = jax.lax.dynamic_index_in_dim(pool, jnp.minimum(src[i], n - 1),
                                         axis=ax, keepdims=True)
        pool = jax.lax.dynamic_update_slice_in_dim(
            pool, v, jnp.minimum(dst[i], n - 1), axis=ax)
    return pool


def _flash_grouped_local(q, k, v, window):
    """Single-shard grouped-layout kernel call.
    q: (B,S,kvH,G,hd); k,v: (B,S,kvH,hd) -> (B,S,kvH,G,hd)."""
    B, S, kvH, G, hd = q.shape
    qk = jnp.moveaxis(q, 1, 3).reshape(B * kvH * G, S, hd)
    kk = jnp.moveaxis(k, 1, 2).reshape(B * kvH, S, hd)
    vk = jnp.moveaxis(v, 1, 2).reshape(B * kvH, S, hd)
    bq = bk = max(min(128, S), 1)
    o = flash_attention(qk, kk, vk, bq=bq, bk=bk, window=window)
    return jnp.moveaxis(o.reshape(B, kvH, G, S, hd), 3, 1)


def _flash_grouped_fwd_impl(q, k, v, window):
    """Kernel forward, shard_mapped over the batch axes under a mesh (the
    kernel is a per-device program; GSPMD cannot partition a pallas_call)."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import current_mesh, current_rules, shard_map

    mesh = current_mesh()
    if mesh is None:
        return _flash_grouped_local(q, k, v, window)
    batch_ax = current_rules().get("act_batch") or None
    qs = P(batch_ax, None, None, None, None)
    kvs = P(batch_ax, None, None, None)
    return shard_map(
        lambda q, k, v: _flash_grouped_local(q, k, v, window),
        mesh=mesh, in_specs=(qs, kvs, kvs), out_specs=qs)(q, k, v)


def _ref_grouped(q, k, v, window):
    """Memory-safe jnp oracle used for the backward pass (a production
    deployment adds the flash backward kernel; the dominant fwd win — causal
    block skipping — is already in the Pallas kernel)."""
    from repro.models.layers.attention import _chunked_attn

    S = q.shape[1]
    pos = jnp.arange(S)
    return _chunked_attn(q, k, v, pos, pos, True, window,
                         min(128, S) if S % min(128, S) == 0 else S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_grouped(q, k, v, window):
    return _flash_grouped_fwd_impl(q, k, v, window)


def _flash_fwd(q, k, v, window):
    return _flash_grouped_fwd_impl(q, k, v, window), (q, k, v)


def _flash_bwd(window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: _ref_grouped(q, k, v, window), q, k, v)
    return vjp(g)


_flash_grouped.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_grouped(q, k, v, *, window=None):
    """Differentiable grouped-layout flash attention (custom VJP).

    q: (B,S,kvH,G,hd); k,v: (B,S,kvH,hd) -> (B,S,kvH,G,hd)."""
    return _flash_grouped(q, k, v, window)


def rmsnorm(x, scale, *, eps=1e-6):
    return _rn.rmsnorm(x, scale, eps=eps, interpret=default_interpret())
