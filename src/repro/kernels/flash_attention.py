"""Flash attention (causal, GQA, optional sliding window) as a Pallas kernel.

TPU adaptation of the classic GPU flash algorithm (DESIGN.md §2): instead of
warp-level shuffles, the online softmax state (m, l, acc) lives in VMEM
scratch across the sequential K-block grid dimension; the (bq × bk) score
tile is MXU-shaped.  Fully-masked K blocks are skipped with pl.when — this
is what removes the 2× causal overcount of the jnp fallback path (visible in
EXPERIMENTS.md §Perf).

Layouts: q (BH, S, hd); k, v (BKV, S, hd) with BH = B·kvH·G, BKV = B·kvH.
Grid = (BH, nq, nk), K innermost.

The paged serving kernel (``ragged_paged_flash``; the two-phase decode
step calls it with one query row per slot) additionally supports **int8
quantized KV pools**: when the pool dtype is int8, per-entry-per-KV-head
float32 scale pools (``ks``/``vs``, shape (n_pages, kvH, 1, page)) ride in
through the same block-table indirection, and each page tile's scales are
applied IN VMEM right after its DMA, feeding the unchanged fp32
online-softmax accumulate.  HBM traffic per page is therefore the int8 bytes plus one
scale row (~hd/4× less than fp32 KV), never a dequantized copy.

``interpret=None`` on every entry point resolves through
``kernels.ops.default_interpret()``: compiled for real on TPU backends,
interpret mode elsewhere (CPU CI).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._interpret import default_interpret

NEG_INF = -1e30

# the paged serving kernel's op name in the compiled program (the
# ``tpu_custom_call`` instruction is named after it), by which a profiler
# trace's ``XLA Ops`` line and a reduction of it find the kernel
RAGGED_KERNEL = "_ragged_paged_flash"


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, nk: int, scale: float, window):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk

    # causal block skip: K block strictly above the diagonal contributes 0
    in_reach = k_start <= q_start + bq - 1
    if window is not None:  # block entirely older than the window
        in_reach &= (q_start - (k_start + bk - 1)) < window

    @pl.when(in_reach)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = rows >= cols
        if window is not None:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot(p.astype(v_ref.dtype).astype(jnp.float32),
                                      v_ref[0].astype(jnp.float32)))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Paged flash attention (serving): query tokens from arbitrary slots attend
# over a block-table-indexed KV pool.  The kernel-level half of the engine's
# single ragged program: each query row carries its own slot index and
# visible length, so prefill-chunk tokens and decode tokens run through the
# same grid.  The slot index and block table ride in as scalar prefetch and
# resolve the row's pool page in the BlockSpec index_map (a double
# indirection: row -> slot -> page -> pool row) before each grid step's DMA;
# the kernel body only ever sees a dense (page, hd) tile of one KV head.
# Grid = (rows, kvH, pps), pages innermost (sequential online-softmax state
# in VMEM).
#
# Pool layout is HEAD-MAJOR: kp/vp (n_pages, kvH, page, hd), int8 scale
# pools ks/vs (n_pages, kvH, 1, page).  Mosaic requires the last two dims of
# every block to be (8k, 128k)-divisible or whole, so a page tile must be a
# (page, hd) slab of one head; a page-major pool would need a (page, 1, hd)
# block with a size-1 second-minor dim, which Mosaic refuses.


def _ragged_decode_kernel(slot_ref, lens_ref, ptab_ref, q_ref, k_ref, v_ref,
                          *rest, page: int, scale: float, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    t, ji = pl.program_id(0), pl.program_id(2)

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # visible entries of this page for this row: positions 0..lens-1 are
    # contiguous per slot, so the causal mask is just a length cutoff —
    # intra-pack keys written at positions beyond this token stay invisible
    n_valid = lens_ref[t] - ji * page

    @pl.when(n_valid > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (page, hd)
        v = v_ref[0, 0].astype(jnp.float32)  # (page, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, page)
        if quantized:  # fused dequant: a per-entry scale is a column scale
            s = s * ks_ref[0, 0]  # (1, page)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < n_valid, s, NEG_INF)

        m_prev = m_ref[...]  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(cols < n_valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = p * vs_ref[0, 0] if quantized else p
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(pv, v)
        m_ref[...] = m_new

    @pl.when(ji == pl.num_programs(2) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks=None, vs=None, *,
                       interpret=None):
    """Ragged-pack attention over a paged KV pool (one serving tick).

    q: (T, kvH, G, hd) — T pack tokens from arbitrary slots; slot: (T,)
    int32 per-token slot index; lens: (T,) int32 visible entries for each
    token (``q_pos + 1``; 0 = invalid token, output is zeros);
    kp, vp: (n_pages, kvH, page, hd); ptab: (B, pps) int32 block table
    (entries >= n_pages = unmapped).  Returns (T, kvH, G, hd).  Full
    (non-windowed) causal layers only.

    Refcounted prefix-shared pages (serve.engine) require NO kernel change:
    every K/V tile is fetched through the token -> slot -> page indirection
    above, so block-table rows of different slots aliasing the same pool
    page read the same bytes, and copy-on-write happens before the step in
    the allocator (a ``kernels.ops.copy_pages`` call), never in here.

    int8 quantized pools require only the scale side channel: pass
    ``ks``/``vs`` ((n_pages, kvH, 1, page) float32, one scale per pool entry
    per KV head).  The page tile is DMA'd as int8 and its scales applied in
    VMEM — K's to the (G, page) scores, V's to the probabilities — so the
    fp32 online-softmax accumulate is unchanged while HBM traffic per page
    is the int8 bytes plus one scale row, never a dequantized copy.
    """
    if interpret is None:  # resolve outside the jit boundary (static key)
        interpret = default_interpret()
    return _ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks, vs,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks, vs, *, interpret):
    T, kvH, G, hd = q.shape
    npages, page = kp.shape[0], kp.shape[2]
    pps = ptab.shape[1]
    quantized = ks is not None

    def _page_idx(t, h, j, slot_ref, lens_ref, ptab_ref):
        # row -> slot -> page -> pool row; unmapped sentinel pages clamp
        # to a real row whose entries are dead via the lens cutoff
        return (jnp.minimum(ptab_ref[slot_ref[t], j], npages - 1), h, 0, 0)

    row = pl.BlockSpec((1, 1, G, hd), lambda t, h, j, *_: (t, h, 0, 0))
    in_specs = [row, pl.BlockSpec((1, 1, page, hd), _page_idx),
                pl.BlockSpec((1, 1, page, hd), _page_idx)]
    args = [slot, lens, ptab, q, kp, vp]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, page), _page_idx)] * 2
        args += [ks, vs]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T, kvH, pps),
        in_specs=in_specs,
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_decode_kernel, page=page, scale=hd ** -0.5,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, kvH, G, hd), q.dtype),
        interpret=interpret,
        name=RAGGED_KERNEL,
    )(*args)


def flash_attention(q, k, v, *, bq: int = 128, bk: int = 128, window=None,
                    interpret=None):
    """Causal flash attention.

    q: (BH, S, hd); k, v: (BKV, S, hd); BH must be a multiple of BKV
    (grouped queries).  Returns (BH, S, hd).
    """
    if interpret is None:  # resolve outside the jit boundary (see above)
        interpret = default_interpret()
    return _flash_attention(q, k, v, bq=bq, bk=bk, window=window,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "window", "interpret"))
def _flash_attention(q, k, v, *, bq, bk, window, interpret):
    BH, S, hd = q.shape
    BKV = k.shape[0]
    assert BH % BKV == 0
    G = BH // BKV
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = hd ** -0.5

    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                          window=window),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // G, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
