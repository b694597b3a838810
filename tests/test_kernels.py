"""Per-kernel allclose vs the pure-jnp oracles, over shape/dtype sweeps,
plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.models.layers.attention import _gather_paged_kv, _paged_masked_attn

KEY = jax.random.PRNGKey(0)


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# matmul


@pytest.mark.parametrize("shape", [(64, 64, 64), (128, 96, 32), (100, 130, 70),
                                   (256, 512, 128), (32, 1024, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("accum", ["vmem", "hbm"])
def test_matmul_allclose(shape, dtype, accum):
    M, K, N = shape
    a = jax.random.normal(KEY, (M, K), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), dtype)
    got = ops.matmul(a, b, block=(32, 64, 32), accum=accum)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("block", [(16, 16, 16), (32, 64, 32), (128, 128, 128)])
def test_matmul_block_invariance(block):
    a = jax.random.normal(KEY, (96, 160), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (160, 64), jnp.float32)
    got = ops.matmul(a, b, block=block)
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(8, 64), k=st.integers(8, 96), n=st.integers(8, 48))
def test_matmul_linearity(m, k, n):
    """Property: matmul(a, b1 + b2) == matmul(a, b1) + matmul(a, b2)."""
    a = jax.random.normal(KEY, (m, k), jnp.float32)
    b1 = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    b2 = jax.random.normal(jax.random.PRNGKey(2), (k, n), jnp.float32)
    lhs = ops.matmul(a, b1 + b2, block=(16, 16, 16))
    rhs = ops.matmul(a, b1, block=(16, 16, 16)) + ops.matmul(a, b2, block=(16, 16, 16))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("S,hd,G,kvH", [(64, 16, 1, 2), (64, 32, 4, 2),
                                        (128, 16, 2, 3)])
@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_allclose(S, hd, G, kvH, window, dtype):
    B = 2
    q = jax.random.normal(KEY, (B * kvH * G, S, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(2), (B * kvH, S, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (B * kvH, S, hd), dtype)
    got = ops.flash_attention(q, k, v, bq=32, bk=32, window=window)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_block_invariance():
    q = jax.random.normal(KEY, (4, 128, 16), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 16), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 16), jnp.float32)
    o1 = ops.flash_attention(q, k, v, bq=16, bk=64)
    o2 = ops.flash_attention(q, k, v, bq=128, bk=16)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(sq=st.sampled_from([32, 64]), hd=st.sampled_from([8, 16]),
       g=st.integers(1, 3))
def test_flash_causality(sq, hd, g):
    """Property: output at position t is unaffected by future K/V."""
    q = jax.random.normal(KEY, (g, sq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, sq, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, sq, hd), jnp.float32)
    o1 = ops.flash_attention(q, k, v, bq=16, bk=16)
    t = sq // 2
    k2 = k.at[:, t + 1:].set(99.0)
    v2 = v.at[:, t + 1:].set(-99.0)
    o2 = ops.flash_attention(q, k2, v2, bq=16, bk=16)
    np.testing.assert_allclose(o1[:, : t + 1], o2[:, : t + 1],
                               rtol=1e-5, atol=1e-5)


def _gather_rows(pool, ptab):
    """Head-major pool (n_pages, kvH, page, hd) -> per-slot token rows
    (B, pps * page, kvH, hd) through the block table."""
    x = jnp.take(pool, ptab, axis=0, mode="clip")  # (B, pps, kvH, page, hd)
    B, pps, kvH, page, hd = x.shape
    return jnp.swapaxes(x, 2, 3).reshape(B, pps * page, kvH, hd)


def _paged_decode_ref(q, kp, vp, ptab, lens):
    """jnp oracle: gather the block table, mask by fill count, softmax."""
    B, kvH, G, hd = q.shape
    pps, page = ptab.shape[1], kp.shape[2]
    k = _gather_rows(kp, ptab)
    v = _gather_rows(vp, ptab)
    s = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    mask = jnp.arange(pps * page)[None] < lens[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize("page,pps", [(8, 4), (16, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode_allclose(page, pps, dtype):
    """Block-table indirection + partial-page masking vs the gather oracle,
    including unmapped sentinel pages and an empty slot."""
    B, kvH, G, hd = 3, 2, 4, 16
    npages = B * pps
    q = jax.random.normal(KEY, (B, kvH, G, hd), dtype)
    kp = jax.random.normal(jax.random.PRNGKey(1), (npages, kvH, page, hd), dtype)
    vp = jax.random.normal(jax.random.PRNGKey(2), (npages, kvH, page, hd), dtype)
    rng = np.random.RandomState(0)
    perm = rng.permutation(npages)
    ptab = np.full((B, pps), npages, np.int32)  # sentinel = unmapped
    lens = np.asarray([pps * page, 1 + page // 2, 0], np.int32)
    for b in range(B):
        used = -(-int(lens[b]) // page)
        ptab[b, :used] = perm[b * pps:b * pps + used]
    # the decode step's call: one query row per slot
    got = ops.ragged_paged_flash(q, kp, vp, jnp.asarray(ptab),
                                 jnp.arange(B, dtype=jnp.int32),
                                 jnp.asarray(lens))
    want = _paged_decode_ref(q, kp, vp, jnp.asarray(ptab), jnp.asarray(lens))
    # empty slot: kernel yields zeros, oracle yields a uniform average —
    # both are "no valid keys"; compare active slots only
    np.testing.assert_allclose(np.asarray(got[:2], np.float32),
                               np.asarray(want[:2], np.float32), **_tol(dtype))
    assert not bool(jnp.isnan(got).any())


def _edge_pack(page, pps, kvH, G, T, seed=3):
    """A pack at the kernel's edges, with its block table: decode
    singletons whose lengths sit at, and one beside, page and KV-tile
    edges, one of length 1 (a length-1 run); a prefill run longer than the
    q tile; an invalid separator, then a second run of slot 1 (one slot in
    two non-adjacent runs, as a draft chain sits apart from its decode
    token); an invalid tail.  Block-table columns past each slot's length
    hold the unmapped sentinel."""
    tq, ppt = fa.ragged_tile_sizes(kvH, G, 16, page, pps, jnp.float32)
    tk, cap = ppt * page, pps * page
    edges = [1, page - 1, page, page + 1, tk - 1, tk, tk + 1, cap]
    fills = [min(x, cap) for x in edges]
    run = min(tq + tq // 2 + 3, T - len(fills) - 6)
    assert run > tq, (run, tq)
    fills.append(cap)  # the prefill slot
    B = len(fills)
    npages = B * pps
    rng = np.random.RandomState(seed)
    perm = rng.permutation(npages)
    ptab = np.full((B, pps), npages, np.int32)
    for b in range(B):
        used = -(-fills[b] // page)
        ptab[b, :used] = perm[b * pps:b * pps + used]
    slot, lens = [], []
    for b in range(B - 1):
        slot.append(b)
        lens.append(fills[b])
    for j in range(run):
        slot.append(B - 1)
        lens.append(cap - run + 1 + j)
    slot += [0, 1, 1]
    lens += [0, fills[1] - 1, fills[1]]
    slot += [0] * (T - len(slot))
    lens += [0] * (T - len(lens))
    return (np.asarray(ptab), np.asarray(slot, np.int32),
            np.asarray(lens, np.int32), npages)


def _jnp_path(q, kp, vp, ptab, slot, lens, ks=None, vs=None):
    """The engine's jnp attention path on a pack: the block-table gather
    (dequantizing int8 pools) and the per-token masked softmax, every key
    at its own position and each token at ``lens - 1``."""
    cache = {"kp": kp, "vp": vp, "ptab": jnp.asarray(ptab)}
    if ks is not None:
        cache.update(ks=ks, vs=vs)
    k, v = _gather_paged_kv(cache, jnp.float32)
    kpos = jnp.broadcast_to(jnp.arange(k.shape[1]), (len(slot), k.shape[1]))
    o = _paged_masked_attn(q.astype(jnp.float32)[:, None], k[slot], v[slot],
                           kpos, jnp.asarray(lens)[:, None] - 1, None)
    return o[:, 0]


def _in_stack(layer, *pools, n_layers=3):
    """Each pool as plane ``layer`` of an ``n_layers`` stack, the other
    planes holding its pages rolled (a read of another plane shows)."""
    return tuple(jnp.stack([p if i == layer else jnp.roll(p, i + 1, axis=0)
                            for i in range(n_layers)]) for p in pools)


def _assert_layer_plane(got, layer, q, kp, vp, ptab, slot, lens, ks=None,
                        vs=None):
    """The kernel reading plane ``layer`` of stacked pools gives exactly
    what it gives on that layer's own pools (``got``)."""
    if layer is None:
        return
    kp, vp = _in_stack(layer, kp, vp)
    if ks is not None:
        ks, vs = _in_stack(layer, ks, vs)
    stacked = ops.ragged_paged_flash(q, kp, vp, jnp.asarray(ptab),
                                     jnp.asarray(slot), jnp.asarray(lens),
                                     ks=ks, vs=vs, layer=jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(stacked, np.float32),
                                  np.asarray(got, np.float32))


# a first, a middle and a last layer of a 3-layer stack
LAYERS = {"layer0": 0, "layer1": 1, "layer2": 2}


@pytest.mark.parametrize("pack,page,pps,kvH,G,dtype,layer", [
    pytest.param("mixed", 8, 4, 2, 4, jnp.float32, None, id="8-4"),
    pytest.param("mixed", 16, 2, 2, 4, jnp.float32, None, id="16-2"),
    pytest.param("edges", 16, 12, 2, 6, jnp.bfloat16, None,
                 id="edges-gqa-bf16"),
    pytest.param("edges", 8, 20, 2, 6, jnp.float32, None,
                 id="edges-gqa-page8"),
    pytest.param("edges", 16, 12, 5, 1, jnp.bfloat16, None,
                 id="edges-tp-bf16"),
] + [pytest.param("edges", 16, 12, kvH, G, jnp.bfloat16, layer,
                  id=f"edges-{name}-bf16-{lid}")
     for name, kvH, G in (("gqa", 2, 6), ("tp", 5, 1))
     for lid, layer in LAYERS.items()])
def test_ragged_paged_flash_allclose(pack, page, pps, kvH, G, dtype, layer):
    """Ragged query packs: per-run slot -> block-table -> pool-row double
    indirection, per-token visible-length masking (intra-pack causality),
    and invalid (lens == 0) tokens yielding zeros.  The two small packs
    check against a gather oracle; the edge packs against the engine's
    jnp path, over several KV tiles and q tiles.  With ``layer``, the same
    pools as one plane of a layer stack, read at that plane's index, give
    the same output to the bit."""
    hd = 16
    if pack == "edges":
        T = 96 if G == 1 else 40
        ptab, slot, lens, npages = _edge_pack(page, pps, kvH, G, T)
        kp = jax.random.normal(jax.random.PRNGKey(1),
                               (npages, kvH, page, hd)).astype(dtype)
        vp = jax.random.normal(jax.random.PRNGKey(2),
                               (npages, kvH, page, hd)).astype(dtype)
        q = jax.random.normal(KEY, (T, kvH, G, hd)).astype(dtype)
        got = ops.ragged_paged_flash(q, kp, vp, jnp.asarray(ptab),
                                     jnp.asarray(slot), jnp.asarray(lens))
        want = _jnp_path(q, kp, vp, ptab, slot, lens)
        valid = lens > 0
        np.testing.assert_allclose(np.asarray(got[valid], np.float32),
                                   np.asarray(want[valid], np.float32),
                                   **_tol(dtype))
        np.testing.assert_allclose(np.asarray(got[~valid], np.float32), 0.0,
                                   atol=1e-6)
        assert not bool(jnp.isnan(got).any())
        _assert_layer_plane(got, layer, q, kp, vp, ptab, slot, lens)
        return
    B, T = 3, 11
    npages = B * pps
    kp = jax.random.normal(jax.random.PRNGKey(1), (npages, kvH, page, hd))
    vp = jax.random.normal(jax.random.PRNGKey(2), (npages, kvH, page, hd))
    q = jax.random.normal(KEY, (T, kvH, G, hd))
    rng = np.random.RandomState(3)
    perm = rng.permutation(npages)
    ptab = np.full((B, pps), npages, np.int32)
    fills = [pps * page, page + 1, 3]  # per-slot written prefix
    for b in range(B):
        used = -(-fills[b] // page)
        ptab[b, :used] = perm[b * pps:b * pps + used]
    # a mixed pack: several tokens per slot at increasing positions, plus
    # one invalid token (lens 0)
    slot = np.asarray([0, 0, 1, 2, 0, 1, 2, 0, 1, 0, 2], np.int32)
    lens = np.zeros(T, np.int32)
    cursor = {b: 1 for b in range(B)}
    for t in range(T - 1):
        b = int(slot[t])
        lens[t] = min(cursor[b], fills[b])
        cursor[b] += rng.randint(1, 4)
    lens[T - 1] = 0  # invalid pack tail

    got = ops.ragged_paged_flash(q, kp, vp, jnp.asarray(ptab),
                                 jnp.asarray(slot), jnp.asarray(lens))
    # oracle: gather each token's slot context, mask by its visible length
    k = _gather_rows(kp, jnp.asarray(ptab))[slot]
    v = _gather_rows(vp, jnp.asarray(ptab))[slot]
    s = jnp.einsum("tkgd,tskd->tkgs", q, k) * hd ** -0.5
    mask = jnp.arange(pps * page)[None] < jnp.asarray(lens)[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    want = jnp.einsum("tkgs,tskd->tkgd", jax.nn.softmax(s, -1), v)
    valid = lens > 0
    np.testing.assert_allclose(np.asarray(got[valid]),
                               np.asarray(want[valid]),
                               **_tol(jnp.float32))
    np.testing.assert_allclose(np.asarray(got[~valid]), 0.0, atol=1e-6)
    assert not bool(jnp.isnan(got).any())


@pytest.mark.parametrize("kernel,layer", [
    pytest.param(kernel, None, id=kernel)
    for kernel in ("paged", "ragged", "edges-gqa", "edges-tp")
] + [pytest.param(kernel, layer, id=f"{kernel}-{lid}")
     for kernel in ("edges-gqa", "edges-tp")
     for lid, layer in LAYERS.items()])
def test_flash_kernels_fused_dequant_match_fp32_pool(kernel, layer):
    """int8 pools: the fused in-VMEM dequant (int8 tile × scale row inside
    the online-softmax loop) must match running the same kernel on the
    dequantized fp32 pool to float tolerance — quantization changes WHERE
    the bytes expand, not the math.  The edge packs also match the jnp
    path on the int8 pool, and with ``layer`` the int8 pools and their
    scales as one plane of a layer stack give the same output to the
    bit."""
    hd = 16
    if kernel.startswith("edges"):
        kvH, G = (2, 6) if kernel == "edges-gqa" else (5, 1)
        page, pps = 16, 12
        T = 96 if G == 1 else 40
        ptab, slot, lens, npages = _edge_pack(page, pps, kvH, G, T, seed=5)
        ptab, slot, lens = (jnp.asarray(x) for x in (ptab, slot, lens))
        q = jax.random.normal(KEY, (T, kvH, G, hd))
    else:
        B, kvH, G, page, pps = 2, 2, 4, 8, 3
        npages = B * pps
        ptab = jnp.asarray(np.arange(npages).reshape(B, pps), jnp.int32)
    kp_f = jax.random.normal(jax.random.PRNGKey(1), (npages, kvH, page, hd))
    vp_f = jax.random.normal(jax.random.PRNGKey(2), (npages, kvH, page, hd))
    kp8, ks = ops.quantize_kv(kp_f)  # scales (npages, kvH, page)
    vp8, vs = ops.quantize_kv(vp_f)
    kp_dq = ops.dequantize_kv(kp8, ks)
    vp_dq = ops.dequantize_kv(vp8, vs)
    ks, vs = ks[:, :, None, :], vs[:, :, None, :]  # scale-pool layout
    if kernel == "paged":
        q = jax.random.normal(KEY, (B, kvH, G, hd))
        lens = jnp.asarray([pps * page, page + 3], jnp.int32)
        slot = jnp.arange(B, dtype=jnp.int32)
    elif kernel == "ragged":
        T = 5
        q = jax.random.normal(KEY, (T, kvH, G, hd))
        slot = jnp.asarray([0, 1, 0, 1, 0], jnp.int32)
        lens = jnp.asarray([1, page, pps * page, 0, page + 2], jnp.int32)
    got = ops.ragged_paged_flash(q, kp8, vp8, ptab, slot, lens, ks=ks, vs=vs)
    want = ops.ragged_paged_flash(q, kp_dq, vp_dq, ptab, slot, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    if kernel.startswith("edges"):
        path = _jnp_path(q, kp8, vp8, ptab, slot, lens, ks, vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(path),
                                   rtol=2e-4, atol=2e-4)
    _assert_layer_plane(got, layer, q, kp8, vp8, ptab, slot, lens, ks, vs)


# the chat cell's per-chip shapes (8-token q tiles, 128-position KV tiles)
# and the tp4 cell's (64-token q tiles)
CHAT = dict(kvH=2, G=6, hd=128, page=16, pps=128, kv_dtype=jnp.bfloat16)
TP4 = dict(CHAT, kvH=5, G=1)


def _pack(pieces, T=128):
    """(slot, lens) of a pack from (slot, first lens, tokens) pieces, one
    token per position; slot -1 is an invalid stretch; invalid tail."""
    slot, lens = [], []
    for b, first, n in pieces:
        slot += [max(b, 0)] * n
        lens += [0] * n if b < 0 else list(range(first, first + n))
    return (np.asarray(slot + [0] * (T - len(slot)), np.int32),
            np.asarray(lens + [0] * (T - len(lens)), np.int32))


@pytest.mark.parametrize("shapes,pieces,runs,tiles", [
    # decode singletons at a length-1, a tile edge and one past it
    (CHAT, [(0, 1, 1), (1, 128, 1), (2, 129, 1)], 3, 1 + 1 + 2),
    # a prefill chunk and its decode token: 21 tokens of one slot, cut at
    # the 8-token q tiles into 8 + 8 + 5, lengths across the tile edge
    (CHAT, [(3, 120, 21)], 3, 1 + 2 + 2),
    # speculation: slot 0's decode token, then a prefill, then its draft
    # chain apart from it, so slot 0 is two runs
    (CHAT, [(0, 50, 1), (1, 300, 1), (2, 10, 3), (0, 51, 2)], 4,
     1 + 3 + 1 + 1),
    # an invalid token between two stretches of one slot splits them
    (CHAT, [(4, 10, 2), (-1, 0, 1), (4, 12, 1)], 2, 2),
    # tp4: 64-token q tiles, so a 70-token run is two
    (TP4, [(0, 1000, 70)], 2, 9 + 9),
], ids=["decode-singletons", "prefill-run", "spec-two-runs", "invalid-gap",
        "tp4-q-tile"])
def test_ragged_visits_hand_built_packs(shapes, pieces, runs, tiles):
    slot, lens = _pack(pieces)
    assert fa.ragged_visits(slot, lens, **shapes) == (runs, tiles)


@pytest.mark.parametrize("shapes", [CHAT, TP4], ids=["chat", "tp4"])
def test_ragged_visits_match_the_kernels_run_table(shapes):
    """The host counter and the kernel's on-device run table agree: same
    run cut, same tile size, on random packs of decode tokens, chunks,
    repeats of one slot and invalid tokens."""
    rng = np.random.RandomState(0)
    tq, ppt = fa.ragged_tile_sizes(shapes["kvH"], shapes["G"], shapes["hd"],
                                   shapes["page"], shapes["pps"],
                                   shapes["kv_dtype"])
    for _ in range(20):
        pieces = [(int(rng.randint(-1, 6)), int(rng.randint(1, 1900)),
                   int(rng.randint(1, 40))) for _ in range(8)]
        slot, lens = _pack(pieces, T=400)
        slot, lens = slot[:128], lens[:128]
        _, table, n_items = fa._run_table(jnp.asarray(slot), jnp.asarray(lens),
                                          tq, ppt * shapes["page"])
        want = (int((np.asarray(table[2]) > 0).sum()), int(n_items[0]))
        assert fa.ragged_visits(slot, lens, **shapes) == want


# ---------------------------------------------------------------------------
# rmsnorm


@pytest.mark.parametrize("shape", [(4, 37, 96), (1, 128), (3, 5, 7, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_allclose(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), jnp.float32)
    got = ops.rmsnorm(x, s)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@settings(max_examples=10, deadline=None)
@given(scale=st.floats(0.1, 100.0), rows=st.integers(1, 8))
def test_rmsnorm_scale_invariance(scale, rows):
    """Property: rmsnorm(αx) == rmsnorm(x) for α > 0."""
    x = jax.random.normal(KEY, (rows, 64), jnp.float32)
    s = jnp.ones((64,))
    np.testing.assert_allclose(ops.rmsnorm(x * scale, s), ops.rmsnorm(x, s),
                               rtol=1e-3, atol=1e-4)
