"""Tiled MXU matmul — the paper's central operation as a Pallas TPU kernel.

The BlockSpec tiling (bm, bk, bn) and the accumulation policy are the
"memory mode" knobs (DESIGN.md §2): how the iteration space hashes onto the
fast near memory (VMEM) mirrors the paper's MCDRAM/NUMA configurations.

  accum="vmem"  ("cache" mode)  — fp32 accumulator lives in a VMEM scratch;
                                  each C tile is written to HBM exactly once.
  accum="hbm"   ("flat" mode)   — C (fp32) is revisited in HBM on every K
                                  step; max HBM traffic, min VMEM footprint.

Grid = (M/bm, N/bn, K/bk), K innermost (sequential on TPU, so accumulation
across K steps is well-defined).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._interpret import default_interpret


def _kernel_vmem(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_hbm(a_ref, b_ref, o_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32).astype(o_ref.dtype)


def matmul(a, b, *, block=(256, 256, 256), accum="vmem", interpret=None,
           out_dtype=None):
    """C = A·B with explicit VMEM tiling.  A: (M,K), B: (K,N).

    ``interpret=None`` resolves through ``kernels.ops.default_interpret()``:
    compiled on TPU backends, interpret mode elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    return _matmul(a, b, block=block, accum=accum, interpret=interpret,
                   out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("block", "accum", "interpret",
                                             "out_dtype"))
def _matmul(a, b, *, block, accum, interpret, out_dtype):
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bk, bn = (min(block[0], M), min(block[1], K), min(block[2], N))
    pm, pk, pn = (-M) % bm, (-K) % bk, (-N) % bn
    if pm or pk or pn:  # zero-pad to tile multiples (zeros are matmul-safe)
        a = jnp.pad(a, ((0, pm), (0, pk)))
        b = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp = a.shape
    Np = b.shape[1]
    k_steps = Kp // bk
    grid = (Mp // bm, Np // bn, k_steps)
    out_dtype = out_dtype or a.dtype

    if accum == "vmem":
        out = pl.pallas_call(
            functools.partial(_kernel_vmem, k_steps=k_steps),
            grid=grid,
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                      pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(a, b)
    else:  # "hbm": fp32 output revisited per K step, cast at the end
        out = pl.pallas_call(
            functools.partial(_kernel_hbm, k_steps=k_steps),
            grid=grid,
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                      pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            interpret=interpret,
        )(a, b).astype(out_dtype)

    if pm or pn:
        out = out[:M, :N]
    return out
