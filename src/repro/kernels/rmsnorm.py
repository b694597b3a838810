"""Fused RMSNorm Pallas kernel (row-blocked, f32 statistics in-register)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._interpret import default_interpret


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret=None):
    """x: (..., D); scale: (D,).

    ``interpret=None`` resolves through ``kernels.ops.default_interpret()``:
    compiled on TPU backends, interpret mode elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    return _rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _rmsnorm(x, scale, *, eps, block_rows, interpret):
    shape = x.shape
    D = shape[-1]
    R = 1
    for d in shape[:-1]:
        R *= d
    x2 = x.reshape(R, D)
    br = min(block_rows, R)
    pr = (-R) % br
    if pr:
        x2 = jnp.pad(x2, ((0, pr), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(x2.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, scale)
    if pr:
        out = out[:R]
    return out.reshape(shape)
