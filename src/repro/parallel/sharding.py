"""Logical-axis sharding: the framework's single source of truth for layout.

Model code never mentions mesh axes.  It tags tensors with *logical* axis
names via ``lshard(x, "act_batch", "act_seq", None)``; a rules table maps
logical names to physical mesh axes.  Parameter layouts are derived from leaf
*names* (every weight leaf has a descriptive name) via ``PARAM_AXES``.

This indirection is the TPU analogue of the paper's pinning discipline: the
rules table decides, once, system-wide, how work binds to the machine — model
authors just write math (the paper's ``C = A*B`` users), operators set rules
(the paper's systems staff setting KMP_AFFINITY/taskset/memory-mode).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — what every call
    site in the repo wants: per-shard execution, no verification that
    replicated outputs agree across shards."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Ambient mesh + rules (thread-local so tests can nest)

_state = threading.local()


def _stack():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


def current_mesh() -> Optional[Mesh]:
    st = _stack()
    return st[-1][0] if st else None


def current_rules() -> Dict[str, Any]:
    st = _stack()
    return st[-1][1] if st else {}


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[Dict[str, Any]] = None):
    """Enter ``mesh`` (jax context) and install logical-axis rules."""
    if rules is None:
        rules = make_rules(mesh)
    _stack().append((mesh, rules))
    try:
        with mesh:
            yield mesh
    finally:
        _stack().pop()


@contextlib.contextmanager
def axis_rules(rules: Dict[str, Any]):
    """Override logical-axis rules inside the current mesh (e.g. SP decode)."""
    mesh = current_mesh()
    merged = dict(current_rules())
    merged.update(rules)
    _stack().append((mesh, merged))
    try:
        yield
    finally:
        _stack().pop()


# ---------------------------------------------------------------------------
# Rules

def make_rules(mesh: Mesh, *, heads_tp: bool = False, kv_seq_axis=None,
               decode: bool = False, long_ctx: bool = False) -> Dict[str, Any]:
    """Default logical→physical rules for a (pod,)data×model mesh.

    ``heads_tp``     — shard attention heads over 'model' (requires
                       num_heads % model_size == 0; the autotuner turns this
                       on per-arch).  Off = universal batch-local attention.
    ``kv_seq_axis``  — shard the KV-cache sequence dim (long-context SP).
    ``decode``       — weight-stationary serving layout: per-token
                       activations are MBs while FSDP-gathered weights are
                       GBs, so activations REPLICATE over 'data' and matmuls
                       contract over the data-sharded weight dim (psum of
                       activations).  KV caches / recurrent states stay
                       batch-sharded over 'data' ("act_kv_batch") and
                       KV-seq-sharded over 'model'; attention is local per
                       batch shard via the distributed flash-decode.
                       Measured: arctic-480b decode collectives drop from
                       81 GB/token (batch-sharded acts + weight gathers) to
                       activation-sized psums (EXPERIMENTS.md §Perf).
    ``long_ctx``     — batch=1 long-context serving: batch unshardable, KV
                       sequence sharded over ('data','model').
    """
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    fsdp = batch_axes  # params fully sharded over all data-parallel axes
    kv_batch_axes = batch_axes
    if long_ctx:
        # batch=1 long-context serving: batch unshardable; KV sequence takes
        # every axis and the distributed flash-decode combines partials
        batch_axes = ()
        kv_batch_axes = ()
        kv_seq_axis = kv_seq_axis or ("data", "model")
        decode = True
    elif decode:
        batch_axes = ()  # weight-stationary: step activations replicated
        if kv_seq_axis is None:
            # KV seq over 'model' (KV heads are NOT shardable in general —
            # qwen1.5 has 20, glm4 has 2)
            kv_seq_axis = ("model",)
    rules: Dict[str, Any] = {
        # ---- weights ----
        "fsdp": fsdp,
        "tensor": "model",
        "vocab": "model",
        "expert": "model",
        "layer": None,  # scan-stack dim
        None: None,
        # ---- activations ----
        "act_batch": batch_axes,
        "act_kv_batch": kv_batch_axes,  # decode caches/recurrent states
        "act_seq": None,
        # saved remat boundaries: seq-sharded over 'model' (Megatron-SP style)
        # so stored residuals are not replicated across the TP axis
        "act_res_seq": "model",
        "act_kv_seq": kv_seq_axis,
        "act_embed": None,
        "act_heads": "model" if heads_tp else None,
        # attention weights: replicated across 'model' by default (zero QKV
        # collectives; the weights are small next to FFN/experts).  The
        # autotuner can set this to 'model' (hd-sharding) for memory-starved
        # f32-param archs — measured on qwen1.5: hd-sharding costs 3× in
        # per-use activation gathers, so bf16 storage is preferred instead.
        "attn_hd": None,
        "act_kv_heads": None,
        "act_ff": "model",
        "act_vocab": "model",
        "act_expert": "model",
    }
    return rules


def make_serve_rules(mesh: Mesh) -> Dict[str, Any]:
    """KV-head tensor-parallel rules for the ragged serving engine.

    The paged KV pools (kp/vp and int8 scale pools ks/vs) split along the
    KV-head axis over the mesh's TP axis; everything else — block tables,
    per-slot positions, recurrent states, activations, weights — stays
    replicated, so the engine's host-side bookkeeping (PagePool, scheduler,
    pack vectors) is device-count-agnostic and only attention's per-head
    work shrinks per device.  Attention outputs are constrained back to
    replicated before the output projection, making every collective an
    exact all-gather: no sum depends on the device count.  The invariance
    suite finds the engine bit-identical to the 1-device engine at its
    small CPU sizes; elsewhere a per-head slice of a projection may
    accumulate in another order, and on a TPU at full depth the outputs
    part in the last bits (see PERF.md)."""
    ax = "model" if "model" in mesh.axis_names else mesh.axis_names[-1]
    rules = make_rules(mesh, decode=True)
    rules.update({
        "act_kv_seq": None,  # heads, not sequence, carry the split here
        "act_kv_heads": ax,
        "act_kv_batch": (),  # ptab/kpos/slen replicated: global bookkeeping
        "tensor": None,  # recurrent-state carries stay replicated
        "act_ff": None,
        "act_vocab": None,
        "act_expert": None,
        "vocab": None,
        "expert": None,
        "fsdp": None,  # serving params are replicated (weight-stationary)
    })
    return rules


def logical_spec(names: Sequence[Optional[str]], rules=None) -> P:
    rules = rules if rules is not None else current_rules()
    out = []
    for n in names:
        r = rules.get(n, None) if n is not None else None
        out.append(r)
    return P(*out)


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes from dims they don't divide (replicate instead).

    pjit argument shardings require exact divisibility; model dims like a
    4/3-projection d_ff or tiny head counts can be indivisible by an axis.
    Axes are dropped right-to-left until the dim divides.
    """
    out = []
    for i, entry in enumerate(tuple(spec)):
        if entry is None:
            out.append(None)
            continue
        axes = list(entry) if isinstance(entry, tuple) else [entry]
        def tot(ax):
            n = 1
            for a in ax:
                n *= mesh.shape[a]
            return n
        while axes and shape[i] % tot(axes) != 0:
            axes.pop()
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def lshard(x, *names: Optional[str]):
    """Constrain ``x`` to the logical axes ``names`` (no-op outside a mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_spec(names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter layouts, derived from leaf names

# leaf name -> logical axes of the *unstacked* leaf (scan adds a "layer" dim)
PARAM_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    # embeddings / heads
    "tok_embed": ("vocab", "fsdp"),
    "pos_embed": (None, None),
    "out_head": ("fsdp", "vocab"),
    "frontend_proj": (None, "fsdp"),
    # norms
    "scale": (None,),
    "bias": (None,),
    # attention (grouped layout; exactly one of "act_heads"/"attn_hd" maps
    # to 'model' depending on the heads-TP rule)
    "wq": ("fsdp", None, "act_heads", "attn_hd"),
    "wk": ("fsdp", None, "attn_hd"),
    "wv": ("fsdp", None, "attn_hd"),
    "wo": (None, "act_heads", "attn_hd", "fsdp"),
    "bq": (None, "act_heads", "attn_hd"),
    "bk": (None, "attn_hd"),
    "bv": (None, "attn_hd"),
    # dense mlp
    "w_gate": ("fsdp", "tensor"),
    "w_up": ("fsdp", "tensor"),
    "w_down": ("tensor", "fsdp"),
    # moe
    "router": ("fsdp", None),
    "we_gate": ("expert", "fsdp", None),
    "we_up": ("expert", "fsdp", None),
    "we_down": ("expert", None, "fsdp"),
    # mamba
    "in_proj": ("fsdp", "tensor"),
    "conv_w": (None, "tensor"),
    "conv_b": ("tensor",),
    "x_proj": ("tensor", None),
    "dt_w": (None, "tensor"),
    "dt_b": ("tensor",),
    "A_log": ("tensor", None),
    "ssm_D": ("tensor",),
    "out_proj": ("tensor", "fsdp"),
    # xlstm
    "up_proj": ("fsdp", "tensor"),
    "xq": ("tensor", None),
    "xk": ("tensor", None),
    "xv": ("tensor", None),
    "wi": ("tensor", None),
    "wf": ("tensor", None),
    "bi": (None,),
    "bf": (None,),
    "wo_gate": ("tensor", None),
    "down_proj": ("tensor", "fsdp"),
    "w_ifzo": ("fsdp", "tensor"),
    "r_ifzo": (None, "tensor"),
    "b_ifzo": ("tensor",),
    "skip_scale": (None,),
}


def _leaf_spec(path, leaf, rules, mesh=None) -> P:
    name = None
    for p in reversed(path):
        if isinstance(p, jax.tree_util.DictKey):
            name = p.key
            break
    if name is None or name not in PARAM_AXES:
        raise ValueError(f"no sharding rule for param leaf at path {path}")
    axes = PARAM_AXES[name]
    ndim = len(leaf.shape)
    if ndim == len(axes) + 1:  # scan-stacked
        axes = ("layer",) + axes
    elif ndim != len(axes):
        raise ValueError(
            f"param {name} has ndim {ndim}, rule expects {len(axes)} (+1 stacked)"
        )
    spec = logical_spec(axes, rules)
    if mesh is not None:
        spec = sanitize_spec(spec, leaf.shape, mesh)
    return spec


def param_specs(params, mesh: Optional[Mesh] = None, rules=None):
    """PartitionSpec pytree for a params pytree (works on ShapeDtypeStructs)."""
    mesh = mesh or current_mesh()
    rules = rules if rules is not None else (current_rules() or (make_rules(mesh) if mesh else {}))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(path, leaf, rules, mesh), params
    )


def constrain_like_params(tree):
    """with_sharding_constraint a (grad) tree to the params' sharding rules.

    Without this, GSPMD is free to keep gradients replicated across the
    'model' axis through the whole backward + optimizer (measured: 48 GiB/dev
    of replicated grads on jamba-398b).  No-op outside a mesh.
    """
    mesh = current_mesh()
    if mesh is None:
        return tree
    specs = param_specs(tree, mesh)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, P))


def param_shardings(params, mesh: Optional[Mesh] = None, rules=None):
    mesh = mesh or current_mesh()
    specs = param_specs(params, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
