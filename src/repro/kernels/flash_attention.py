"""Flash attention (causal, GQA, optional sliding window) as a Pallas kernel.

TPU adaptation of the classic GPU flash algorithm (DESIGN.md §2): instead of
warp-level shuffles, the online softmax state (m, l, acc) lives in VMEM
scratch across the sequential K-block grid dimension; the (bq × bk) score
tile is MXU-shaped.  Fully-masked K blocks are skipped with pl.when — this
is what removes the 2× causal overcount of the jnp fallback path (visible in
EXPERIMENTS.md §Perf).

Layouts: q (BH, S, hd); k, v (BKV, S, hd) with BH = B·kvH·G, BKV = B·kvH.
Grid = (BH, nq, nk), K innermost.

The paged serving kernel (``ragged_paged_flash``) walks runs of pack
tokens and, for each, only the KV tiles its length reaches (see its
section below).
It also supports **int8 quantized KV pools**: when the pool dtype is int8,
per-entry-per-KV-head float32 scale pools (``ks``/``vs``, shape
(n_pages, kvH, 1, page)) ride in through the same block table, and each
KV tile's scales are applied IN VMEM right after its DMA, feeding the
unchanged fp32 online-softmax accumulate.  HBM traffic per page is
therefore the int8 bytes plus its scales (~hd/4× less than fp32 KV),
never a dequantized copy.

``interpret=None`` on every entry point resolves through
``kernels.ops.default_interpret()``: compiled for real on TPU backends,
interpret mode elsewhere (CPU CI).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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
# same kernel.
#
# Iteration: RUNS, not rows.  The wrapper cuts the pack into runs, maximal
# stretches of consecutive valid tokens of one slot split at the pack's
# q-tile boundaries (``tq`` tokens), and prefetches the run table as
# scalars.  The kernel walks, run by run, only the KV tiles (``ppt`` pages
# each) that the run's longest row can see: ceil(run_kv / (ppt·page)), a
# dynamic trip count.  Pages come by manual DMA through the block table
# (run -> slot -> page -> pool row), one copy per page per pool,
# double-buffered across the flattened (run, tile) sequence so the next
# tile's copies are in flight while this one computes.  All query rows of
# the run's q tile (tq tokens × G heads of one KV head) attend together
# against each KV tile; the causal mask is a per-row length cutoff, 0 for
# the tile's rows outside the run, and an all-masked row leaves its
# online-softmax state exactly as it was.  The work is Σ_runs
# ceil(run_kv / tk) tiles, not a static T × kvH × pps grid: a decode token
# costs its own length, and a prefill chunk streams its slot's pages once
# per q tile instead of once per token.
#
# Pool layout is HEAD-MAJOR: kp/vp (n_pages, kvH, page, hd), int8 scale
# pools ks/vs (n_pages, kvH, 1, page).  All KV heads of one page are one
# contiguous slab, so a page is one DMA per pool, and the KV-head loop runs
# inside the kernel.  A scanned stage stacks its layers' pools, (L, n_pages,
# ...); the kernel takes the stack whole with the layer as a scalar and
# DMAs from that layer's plane.

# a q tile aims at this many query rows (tq tokens × G heads)
_Q_ROWS = 64
# a KV tile aims at this many positions: one MXU-wide score tile
_KV_POSITIONS = 128
# VMEM for the double-buffered K and V tiles
_KV_BUFFER_BYTES = 4 << 20


def ragged_tile_sizes(kvH: int, G: int, hd: int, page: int, pps: int,
                      kv_dtype) -> tuple:
    """``(tq, ppt)``: pack tokens per q tile and pages per KV tile of
    ``ragged_paged_flash``, from static shapes alone.  ``tq·G`` query rows
    fill whole bf16 sublane tiles (a multiple of 16); ``ppt`` pages make a
    ~128-position KV tile no wider than the block table, halved while the
    double-buffered K and V tiles exceed their VMEM budget."""
    tq = 1
    while tq * 2 * G <= _Q_ROWS:
        tq *= 2
    while tq * G % 16:
        tq *= 2
    ppt = max(1, min(pps, _KV_POSITIONS // page))
    tile_bytes = 2 * 2 * kvH * page * hd * jnp.dtype(kv_dtype).itemsize
    while ppt > 1 and ppt * tile_bytes > _KV_BUFFER_BYTES:
        ppt //= 2
    return tq, ppt


def _run_starts(slot, lens, tq: int, xp):
    """The tokens that open a run: valid, and first in the pack or in a q
    tile, or after a token that is invalid or of another slot.  ``xp`` is
    numpy (the engine's counter) or jax.numpy (the kernel's wrapper)."""
    valid = lens > 0
    joins = xp.concatenate([xp.zeros(1, bool),
                            valid[:-1] & (slot[1:] == slot[:-1])])
    return valid & ((xp.arange(slot.shape[0]) % tq == 0) | ~joins)


def ragged_visits(slot, lens, *, kvH: int, G: int, hd: int, page: int,
                  pps: int, kv_dtype) -> tuple:
    """``(runs, kv_tiles)`` that one ``ragged_paged_flash`` call visits for
    a pack, on the host with the kernel's own run cut and tile sizes: the
    engine's ``attn_runs`` and ``attn_kv_tiles`` counters."""
    slot, lens = np.asarray(slot), np.asarray(lens)
    tq, ppt = ragged_tile_sizes(kvH, G, hd, page, pps, kv_dtype)
    starts = _run_starts(slot, lens, tq, np)
    rid = np.cumsum(starts) - 1
    valid = lens > 0
    run_kv = np.zeros(int(starts.sum()), np.int64)
    np.maximum.at(run_kv, rid[valid], lens[valid])
    tk = ppt * page
    return len(run_kv), int((-(-run_kv // tk)).sum())


def _run_table(slot, lens, tq: int, tk: int):
    """The kernel's iteration, built on the device from the pack: each
    token's run id (-1 if invalid); the run table (3, T) of each run's
    slot, q tile and KV length (its largest lens), runs past the count
    having length 0; and the (1,) count of (run, KV tile) items."""
    T = slot.shape[0]
    valid = lens > 0
    rid = jnp.where(valid, jnp.cumsum(_run_starts(slot, lens, tq, jnp)) - 1,
                    -1)
    at = jnp.where(valid, rid, T)
    runs = jnp.zeros((3, T), jnp.int32)
    runs = runs.at[0, at].set(slot, mode="drop")
    runs = runs.at[1, at].set(jnp.arange(T, dtype=jnp.int32) // tq,
                              mode="drop")
    runs = runs.at[2, at].max(lens, mode="drop")
    return rid, runs, jnp.sum(-(-runs[2] // tk), keepdims=True)


def _ragged_kernel(runs_ref, n_items_ref, ptab_ref, layer_ref, q_ref, rlen_ref,
                   rrun_ref, *rest, rq: int, ppt: int, page: int,
                   scale: float, quantized: bool):
    if quantized:
        (kp, vp, ks, vs, o_ref, kbuf, vbuf, ksbuf, vsbuf, sems,
         m_ref, l_ref, acc_ref) = rest
        scales = ((ks, ksbuf), (vs, vsbuf))
    else:
        kp, vp, o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref = rest
        scales = ()
    kvH, _, hd = q_ref.shape
    npages, pps = kp.shape[1], ptab_ref.shape[1]
    tk = ppt * page
    n_items = n_items_ref[0]
    layer = layer_ref[0]

    def copies(r, j, b):
        """Tile j of run r as DMAs into buffer b: one per page per value
        pool, from the layer's plane of the stacked pool, and, for int8,
        one (kvH, 1, tk) row of the slot's scales per scale pool.  Columns
        past the block table clamp to its last entry and unmapped sentinel
        pages to a real pool row: their entries lie beyond every row's
        length and are masked."""
        s = runs_ref[0, r]
        out = []
        for i in range(ppt):
            pid = jnp.minimum(ptab_ref[s, jnp.minimum(j * ppt + i, pps - 1)],
                              npages - 1)
            out += [pltpu.make_async_copy(src.at[layer, pid], buf.at[b, :, i],
                                          sems.at[n, b])
                    for n, (src, buf) in enumerate(((kp, kbuf), (vp, vbuf)))]
        out += [pltpu.make_async_copy(src.at[s, :, :, pl.ds(j * tk, tk)],
                                      buf.at[b], sems.at[n, b])
                for n, (src, buf) in enumerate(scales, 2)]
        return out

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_items > 0)
    def _first():
        for c in copies(0, 0, 0):
            c.start()

    def item(i, carry):
        r, j = carry
        b = i % 2
        last = (j + 1) * tk >= runs_ref[2, r]
        nr = jnp.where(last, r + 1, r)
        nj = jnp.where(last, 0, j + 1)

        @pl.when(i + 1 < n_items)
        def _prefetch():
            for c in copies(nr, nj, 1 - b):
                c.start()

        for c in copies(r, j, b):
            c.wait()
        rows = pl.ds(pl.multiple_of(runs_ref[1, r] * rq, 16), rq)
        # per-row length cutoff: a row's own visible length inside the run,
        # 0 outside it (intra-pack keys beyond a row stay invisible)
        cut = jnp.where(rrun_ref[rows, :] == r, rlen_ref[rows, :], 0)
        cols = j * tk + jax.lax.broadcasted_iota(jnp.int32, (rq, tk), 1)
        mask = cols < cut

        def head(h, _):
            q = q_ref[h, rows, :].astype(jnp.float32) * scale  # (rq, hd)
            k = kbuf[b, h].astype(jnp.float32).reshape(tk, hd)
            v = vbuf[b, h].astype(jnp.float32).reshape(tk, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (rq, tk)
            if quantized:  # fused dequant: a per-entry scale is a column scale
                s = s * ksbuf[b, h]  # (1, tk)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h, rows, :]  # (rq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h, rows, :] = (l_ref[h, rows, :] * corr
                                 + jnp.sum(p, axis=1, keepdims=True))
            pv = p * vsbuf[b, h] if quantized else p
            acc_ref[h, rows, :] = acc_ref[h, rows, :] * corr + jax.lax.dot(pv, v)
            m_ref[h, rows, :] = m_new
            return 0

        jax.lax.fori_loop(0, kvH, head, 0)
        return nr, nj

    jax.lax.fori_loop(0, n_items, item, (jnp.int32(0), jnp.int32(0)))
    # rows of no run (invalid tokens) kept acc 0: their output is zeros
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        o_ref.dtype)


def ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks=None, vs=None, *,
                       layer=None, interpret=None):
    """Ragged-pack attention over a paged KV pool (one serving tick).

    q: (T, kvH, G, hd) — T pack tokens from arbitrary slots; slot: (T,)
    int32 per-token slot index; lens: (T,) int32 visible entries for each
    token (``q_pos + 1``; 0 = invalid token, output is zeros);
    kp, vp: (n_pages, kvH, page, hd); ptab: (B, pps) int32 block table
    (entries >= n_pages = unmapped).  Returns (T, kvH, G, hd).  Full
    (non-windowed) causal layers only.

    ``layer``: a scalar index into pools stacked over a scanned stage's
    layers, (L, n_pages, kvH, page, hd) (scales (L, n_pages, kvH, 1,
    page)).  The kernel DMAs its pages from that layer's plane of the
    stacked buffer, so the serving step never slices one layer's pool out.
    None: the pools are one layer's, read as layer 0 of a 1-layer stack.

    Refcounted prefix-shared pages (serve.engine) require NO kernel change:
    every K/V tile is fetched through the run -> slot -> page indirection
    above, so block-table rows of different slots aliasing the same pool
    page read the same bytes, and copy-on-write happens before the step in
    the allocator (a ``kernels.ops.copy_pages`` call), never in here.

    int8 quantized pools require only the scale side channel: pass
    ``ks``/``vs`` ((n_pages, kvH, 1, page) float32, one scale per pool entry
    per KV head).  Pages are DMA'd as int8 with their scales and the scales
    applied in VMEM — K's to the scores, V's to the probabilities — so the
    fp32 online-softmax accumulate is unchanged while HBM traffic per page
    is the int8 bytes plus its scales, never a dequantized copy.
    """
    if interpret is None:  # resolve outside the jit boundary (static key)
        interpret = default_interpret()
    if layer is None:
        kp, vp, ks, vs = (None if x is None else x[None]
                          for x in (kp, vp, ks, vs))
        layer = 0
    return _ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks, vs,
                               jnp.reshape(layer, (1,)).astype(jnp.int32),
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ragged_paged_flash(q, kp, vp, ptab, slot, lens, ks, vs, layer, *,
                        interpret):
    T, kvH, G, hd = q.shape
    npages, page = kp.shape[1], kp.shape[3]
    B, pps = ptab.shape
    quantized = ks is not None
    tq, ppt = ragged_tile_sizes(kvH, G, hd, page, pps, kp.dtype)
    tk, rq = ppt * page, tq * G
    # pad the pack with invalid tokens to whole q tiles
    Tp = -(-T // tq) * tq
    q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0), (0, 0)))
    slot = jnp.pad(slot.astype(jnp.int32), (0, Tp - T))
    lens = jnp.pad(lens.astype(jnp.int32), (0, Tp - T))

    rid, runs, n_items = _run_table(slot, lens, tq, tk)

    # query rows token-major within each KV head: row t·G + g
    rows = Tp * G
    qr = jnp.swapaxes(q, 0, 1).reshape(kvH, rows, hd)
    rlen = jnp.repeat(lens, G)[:, None]
    rrun = jnp.repeat(rid, G)[:, None]

    pools = [kp, vp]
    scratch = [pltpu.VMEM((2, kvH, ppt, page, hd), kp.dtype)] * 2
    if quantized:
        # Mosaic refuses a page's 16-lane slice of the lane-padded scale
        # pool as a DMA source, so the scales ride as per-slot rows gathered
        # through the block table, (B, kvH, 1, pps·page) padded to whole
        # tiles: one lane-aligned (kvH, 1, tk) DMA per tile and scale pool
        cols = -(-pps // ppt) * ppt
        at = jnp.pad(ptab, ((0, 0), (0, cols - pps)), constant_values=npages)

        def slot_rows(x):  # (B, cols, kvH, 1, page) of the layer's plane
            x = x.at[layer[0], at].get(mode="clip")
            return jnp.transpose(x, (0, 2, 3, 1, 4)).reshape(
                B, kvH, 1, cols * page)

        pools += [slot_rows(ks), slot_rows(vs)]
        scratch += [pltpu.VMEM((2, kvH, 1, tk), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((len(pools), 2)),
        pltpu.VMEM((kvH, rows, 1), jnp.float32),
        pltpu.VMEM((kvH, rows, 1), jnp.float32),
        pltpu.VMEM((kvH, rows, hd), jnp.float32),
    ]
    # VMEM: q and out (double-buffered), the f32 state (m and l lane-padded),
    # the two row vectors (double-buffered, lane-padded) and the tile buffers
    lane = 128
    vmem = (4 * kvH * rows * hd * q.dtype.itemsize
            + kvH * rows * (hd + 2 * lane) * 4 + 4 * rows * lane * 4
            + 2 * 2 * kvH * ppt * page * hd * kp.dtype.itemsize
            + (2 * 2 * kvH * 8 * max(tk, lane) * 4 if quantized else 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, rq=rq, ppt=ppt, page=page,
                          scale=hd ** -0.5, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[whole((kvH, rows, hd)), whole((rows, 1)),
                      whole((rows, 1))]
            + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools),
            out_specs=whole((kvH, rows, hd)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((kvH, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=interpret,
        name=RAGGED_KERNEL,
    )(runs, n_items, ptab, layer, qr, rlen, rrun, *pools)
    return jnp.swapaxes(out.reshape(kvH, Tp, G, hd), 0, 1)[:T]


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
