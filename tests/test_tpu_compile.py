"""AOT compiles of the serving kernel and step for a described (not
attached) TPU v5e.

Interpret-mode tests check the kernel's numbers; only the chip compiler
checks its tiling (Mosaic refuses blocks whose last two dims are neither
(8, 128)-aligned nor whole).  These compile the paged flash kernel at
published widths for one v5e chip — qwen2-1.5b (GQA kvH 2, G 6) and one
device's share of qwen1.5-4b under a 4-way KV-head split (kvH 5, G 1),
at a small batch and at each benchmark cell's per-chip batch and pool —
and assert the Pallas kernel is in the compiled program.  The whole
ragged serve step is compiled at each cell's shapes too, with depth cut,
to pin that the KV pools are updated in place: no pool-sized copy, slice
or update in the program, and every pool leaf aliased to an output.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""
import dataclasses
import functools
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.analysis.contracts import (POOL_LEAVES, _compiled_alias_params,
                                      _pool_leaf_indices)
from repro.configs.registry import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.serve_step import STATE_DONATE_ARGNUM, decode_state_specs

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
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


# the ops that may yield a value of a pool's shape in the compiled serve
# step: none of them moves the pool's bytes (a scatter writes only its rows)
_POOL_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             "scatter"}
_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")


def _pool_sized_ops(text, shapes):
    """(name, opcode) of every instruction of the compiled program that
    yields a value of one of ``shapes`` and is not in ``_POOL_OPS``; a
    fusion counts as its fused computation's root."""
    roots, comp, found = {}, None, []
    for ln in text.splitlines():
        if ln and not ln[0].isspace() and ln.rstrip().endswith("{"):
            comp = ln.split()[1 if ln.startswith("ENTRY") else 0].lstrip("%")
            continue
        m = _INST.match(ln)
        if not m:
            continue
        name, shape, op = m.groups()
        if ln.lstrip().startswith("ROOT"):
            roots[comp] = op
        if tuple(int(d) for d in shape.split(",") if d) in shapes:
            calls = re.search(r"calls=%([\w.\-]+)", ln)
            found.append((name, op, calls and calls.group(1)))
    return [(name, op) for name, op, calls in found
            if op not in _POOL_OPS
            and not (op == "fusion" and roots.get(calls) == "scatter")]


def _compile_serve_step(topo, arch, batch, n_pages, chips):
    """The engine's ragged serve step at the cell's published widths and
    per-chip pool, two layers deep, on one chip or on the 2x2 mesh by KV
    head: (compiled, state shapes, arguments)."""
    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16", stages=tuple(
        dataclasses.replace(st, repeats=2) for st in cfg.stages))
    if chips > 1:
        mesh = Mesh(np.array(topo.devices[:chips]), ("model",))
        rep = NamedSharding(mesh, PartitionSpec())
    else:
        mesh, rep = None, SingleDeviceSharding(topo.devices[0])
    eng = ServeEngine(None, cfg, batch_size=batch, cache_len=CACHE_LEN,
                      page_size=PAGE, max_pages=n_pages, token_budget=T,
                      prefill_chunk=32, kv_dtype="bfloat16",
                      flash_decode=True, mesh=mesh)
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda p: M.init_paged_state(
        p, cfg, batch, CACHE_LEN, page_size=PAGE, n_pages=n_pages,
        window_extra=eng.chunk, kv_dtype="bfloat16"), params)
    place = lambda x, sharding: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                     sharding=sharding)
    params = jax.tree.map(lambda x: place(x, rep), params)
    if mesh is None:
        state = jax.tree.map(lambda x: place(x, rep), state)
    else:
        with eng._ctx():
            specs = decode_state_specs(state)
        state = jax.tree.map(lambda x, s: place(x, NamedSharding(mesh, s)),
                             state, specs,
                             is_leaf=lambda x: isinstance(x, PartitionSpec))
    vec = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=rep)
    args = (params, state, vec, vec, vec, vec,
            jax.ShapeDtypeStruct((T,), jnp.bool_, sharding=rep),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=rep))
    with eng._ctx():
        return eng._ragged_step.lower(*args).compile(), state, args


@pytest.fixture(scope="module")
def serve_step(topo):
    """``_compile_serve_step``, compiled once per cell for this module's
    tests."""
    compiled = {}

    def get(*cell):
        if cell not in compiled:
            with pytest.MonkeyPatch.context() as mp:
                # the CPU backend would pick the kernel's interpret mode
                mp.setattr(ops, "default_interpret", lambda: False)
                compiled[cell] = _compile_serve_step(topo, *cell)
        return compiled[cell]

    return get


SERVE_CELLS = pytest.mark.parametrize("arch,batch,n_pages,chips", [
    ("qwen2-1.5b",) + CHAT + (1,),
    ("qwen1.5-4b",) + TP4 + (4,),
], ids=["qwen2-1.5b.chat-cell", "qwen1.5-4b.tp4-cell"])


@SERVE_CELLS
def test_ragged_serve_step_updates_pools_in_place(serve_step, arch, batch,
                                                  n_pages, chips):
    """The serve step (``_compile_serve_step``): no instruction but a
    parameter, a tuple element, a bitcast, the layer loop or an in-place
    scatter yields a value of a stacked pool's or one layer's pool's shape
    (a copy, slice or update there moves a whole pool every tick), and
    every pool leaf of the donated state is aliased to an output."""
    compiled, state, args = serve_step(arch, batch, n_pages, chips)
    text = compiled.as_text()
    _assert_kernel(compiled)

    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if path[-1].key in POOL_LEAVES:
            local = leaf.sharding.shard_shape(leaf.shape)
            shapes |= {local, local[1:]}
    assert len(shapes) == 2, shapes
    assert _pool_sized_ops(text, shapes) == []

    pools = _pool_leaf_indices(args, STATE_DONATE_ARGNUM)
    assert len(pools) == 2
    assert set(pools) <= set(_compiled_alias_params(text)), pools


@SERVE_CELLS
def test_ragged_serve_step_returns_greedy_tokens(serve_step, arch, batch,
                                                 n_pages, chips):
    """Beside the (B, V) logits, which stay on the device, the step returns
    each row's greedy token as s32[B]: all a greedy tick copies to the
    host.  Under the mesh it is replicated like the logits."""
    compiled, _, _ = serve_step(arch, batch, n_pages, chips)
    logits, greedy, _ = compiled.out_info
    assert logits.shape == (batch, get_config(arch).vocab_size)
    assert (greedy.shape, greedy.dtype) == ((batch,), jnp.int32)
    assert greedy.sharding.is_fully_replicated
