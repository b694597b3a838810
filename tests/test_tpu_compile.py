"""AOT compiles of the serving kernel for a described (not attached) TPU v5e.

Interpret-mode tests check the kernel's numbers; only the chip compiler
checks its tiling (Mosaic refuses blocks whose last two dims are neither
(8, 128)-aligned nor whole).  These compile the paged flash kernel at
published widths for one v5e chip — qwen2-1.5b (GQA kvH 2, G 6) and one
device's share of qwen1.5-4b under a 4-way KV-head split (kvH 5, G 1),
at a small batch and at each benchmark cell's per-chip batch and pool —
and assert the Pallas kernel is in the compiled program.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""
import functools
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa

HD, PAGE, T, B, CACHE_LEN = 128, 16, 128, 8, 2048
# the regex by which the benchmark's trace reduction finds the kernel's ops
KERNEL_RE = re.compile(json.loads(
    (Path(__file__).resolve().parents[1] / "bench" / "cells"
     / "qwen2-1.5b.chat.json").read_text())["trace"]["kernel"])
PPS = CACHE_LEN // PAGE
N_PAGES = B * PPS
# each cell's per-chip shapes: (batch, pool pages)
CHAT = (64, 8192)  # qwen2-1.5b.chat
TP4 = (16, 2048)  # qwen1.5-4b.tp4-decode, one chip's 5 of 20 KV heads


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # libtpu writes its logs to a fixed directory outside the checkout
    # unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no topology"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _pool_shapes(sharding, kvH, kv_dtype, n_pages=N_PAGES):
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    pools = [sds((n_pages, kvH, PAGE, HD), kv_dtype)] * 2
    if kv_dtype == jnp.int8:
        pools += [sds((n_pages, kvH, 1, PAGE), jnp.float32)] * 2
    return sds, pools


def _assert_kernel(compiled):
    """The kernel is in the compiled program, under an instruction name
    the benchmark's ``trace.kernel`` finds."""
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    names = [ln.split("=", 1)[0].strip().lstrip("%") for ln in calls]
    assert all(KERNEL_RE.search(n) for n in names), names


@pytest.mark.parametrize("kvH,G,kv_dtype,batch,n_pages", [
    (2, 6, jnp.bfloat16, B, N_PAGES),  # qwen2-1.5b
    (2, 6, jnp.int8, B, N_PAGES),
    (5, 1, jnp.bfloat16, B, N_PAGES),  # qwen1.5-4b, one device of a 4-way split
    (5, 1, jnp.int8, B, N_PAGES),
    (2, 6, jnp.bfloat16) + CHAT,
    (5, 1, jnp.bfloat16) + TP4,
], ids=["qwen2-1.5b-bf16", "qwen2-1.5b-int8", "qwen1.5-4b-tp4-bf16",
        "qwen1.5-4b-tp4-int8", "qwen2-1.5b.chat-cell", "qwen1.5-4b.tp4-cell"])
def test_ragged_paged_flash_compiles_for_v5e(one_chip, kvH, G, kv_dtype, batch,
                                             n_pages):
    """At each cell's exact per-chip shapes too (T 128, 128 pages per
    slot): the kernel's tiles fit VMEM, and every kernel instruction keeps
    the name the cells' ``trace.kernel`` finds."""
    sds, pools = _pool_shapes(one_chip, kvH, kv_dtype, n_pages)
    kp, vp, *scales = pools
    q = sds((T, kvH, G, HD), jnp.bfloat16)
    ptab = sds((batch, PPS), jnp.int32)
    vec = sds((T,), jnp.int32)

    def step(q, kp, vp, ptab, slot, lens, *scales):
        return fa.ragged_paged_flash(q, kp, vp, ptab, slot, lens, *scales,
                                     interpret=False)

    _assert_kernel(jax.jit(step).lower(q, kp, vp, ptab, vec, vec,
                                       *scales).compile())


def test_paged_flash_decode_compiles_for_v5e(one_chip):
    """The two-phase decode step's call: one query row per slot."""
    sds, (kp, vp) = _pool_shapes(one_chip, 2, jnp.bfloat16)
    q = sds((B, 2, 6, HD), jnp.bfloat16)

    def step(q, kp, vp, ptab, lens):
        slot = jnp.arange(B, dtype=jnp.int32)
        return fa.ragged_paged_flash(q, kp, vp, ptab, slot, lens,
                                     interpret=False)

    _assert_kernel(jax.jit(step).lower(
        q, kp, vp, sds((B, PPS), jnp.int32),
        sds((B,), jnp.int32)).compile())
