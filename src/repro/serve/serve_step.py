"""The jit-compiled serving steps + state sharding rules.

Two step builders live here:

- ``make_serve_step`` — the legacy lock-step decode step (one token per
  slot, shared positions; kept for the reference engine path).
- ``make_ragged_step`` — the serving engine's ONE compiled program: a flat
  (T,) token pack in which every entry carries its own (slot, position,
  validity), so any mix of prefill-chunk tokens and decode tokens runs
  through a single trace.  ``width`` (max tokens any slot contributes to a
  pack) and ``flash_decode`` are compile-time constants; everything else is
  data, which is what keeps the program count at exactly one regardless of
  traffic.

Neither the engine's prefix cache nor its pluggable scheduler adds a step
builder: sharing is an allocator concern (``serve.pool.PagePool``) and
scheduling is a host-side ORDERING concern (``serve.scheduler``) — a policy
only permutes which (slot, position) pairs fill the pack vectors.  Block-
table rows of several slots may alias one pool page; the ragged step reads
KV through ptab either way, admission presets kpos/slen for inherited
positions via ``models.model.reset_paged_slots`` (a separate control-plane
program, like the COW page copy ``models.model.copy_kv_pages``), and the
serve-path trace count stays at exactly one for every policy.

The TIERED pool (``host_pages=``) adds two more control-plane programs, the
page movers built by ``make_page_gather`` / ``make_page_insert``: demotion
gathers one page's rows (kp/vp values and int8 ks/vs scale rows together)
out to host RAM, promotion scatters them back into a freshly allocated
device page.  Both take the page id as DATA (one trace each for the
engine's lifetime), and the insert is jitted with the state donated — same
contracts as the COW copy, so tiering never perturbs the serve-path trace
count or the no-copy hot loop.  PREEMPTION rides the same two movers: a
victim's private pages PARK via the demotion gather and resume UNPARKS
them via the promotion insert — swap-to-host adds zero new programs, only
pool bookkeeping (``PagePool.park`` / ``unpark``), so ``stats["traces"]``
stays 1 through preempt/resume cycles too.

SPECULATIVE decoding is, by the same argument, just a packing policy: the
drafter proposes k continuation tokens for a decoding slot and the engine
packs them at the slot's next k positions inside the SAME (T,) budget —
to the compiled program they are indistinguishable from any other valid
(slot, position) entries, and ``logit_idx`` widening from (B,) to (B, R)
merely asks the LM head for R rows per slot instead of one.  Verification
is the forward itself (row j's logits are the model's prediction given
the draft prefix up to j); accept/rollback is host-side bookkeeping plus
ONE more control-plane program, ``make_spec_rollback``, which drops the
kpos/slen metadata of rejected draft rows (``models.model
.rollback_paged_slots``).  No draft, accept, or reject path ever adds a
serve-path trace: ``stats["traces"]`` stays 1 with speculation on.

``STATE_AXES`` names the logical axes of every decode-state leaf — the
lock-step cache (k/v/k_pos/pos) and the ragged/paged engine's leaves (kp/vp
page pools, ptab block tables, kpos per-slot positions, slen fill counts) —
so ``decode_state_specs`` can lay either state out on a mesh.  The ragged
pack's own vectors (tokens/slot/q_pos/seq_idx/valid) are replicated: they
are (T,)-sized control data, not state.

Under the serving engine's ``mesh=`` (rules from ``parallel.sharding
.make_serve_rules``) exactly one logical axis maps to hardware:
"act_kv_heads" — so the page pools and int8 scale pools split along their
KV-head dim while ptab/kpos/slen and the pack vectors replicate.  That
shard-split pool layout is the whole device-side story of serving TP: a
logical page id (what PagePool allocates, refcounts, and evicts) names the
SAME page on every device, each device merely storing its slice of the
page's heads — which is why the host bookkeeping needs no knowledge of the
device count and one traced program serves any mesh size.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelCfg
from repro.models import model as M
from repro.parallel.sharding import logical_spec


# argnum of the decode-state pytree in every step builder's signature: jit
# with ``donate_argnums=(STATE_DONATE_ARGNUM,)`` so the pools update in place
STATE_DONATE_ARGNUM = 1

# The name of each engine program.  A jitted program's module is named
# ``jit_<name>``, which is how a profiler trace's ``XLA Modules`` line and a
# reduction of it tell the programs apart; of these names only the serve
# step's holds "wrapper".
SERVE_STEP = "serve_step_wrapper"
SLOT_RESET = "slot_reset"
PAGE_COPY = "cow_page_copy"
PAGE_GATHER = "page_gather"
PAGE_INSERT = "page_insert"
SPEC_ROLLBACK = "spec_rollback"


def _named(fn, name: str):
    fn.__name__ = fn.__qualname__ = name
    return fn


def make_serve_step(cfg: ModelCfg, *, sp_decode: bool = False):
    def serve_step(params, state, tokens_t):
        return M.decode_step(params, cfg, state, tokens_t, sp_decode=sp_decode)

    return serve_step


def make_ragged_step(cfg: ModelCfg, *, width: int, flash_decode: bool = False):
    """Build the single ragged serving program (see ``models.model.ragged_step``).

    Returns ``f(params, state, tokens, slot, q_pos, seq_idx, valid,
    logit_idx) -> (logits (B, V), greedy (B,) int32, new_state)`` with all
    pack vectors (T,) and ``logit_idx`` (B,); with a (B, R) ``logit_idx``
    the logits are (B, R, V) and ``greedy`` (B, R).  ``greedy`` is each
    logits row's first maximum, taken on the device so that the host copies
    one int32 per row of a greedy request instead of the row's V logits;
    the logits stay on the device for the rows sampled at a temperature.
    Jit it with ``donate_argnums=(1,)``
    (``STATE_DONATE_ARGNUM``) — the KV page pools, their int8 scale pools,
    and the recurrent-state carries dominate the state pytree, and donation
    lets XLA scatter the tick's new entries into the existing buffers
    instead of copying the whole pool every tick (the hot-loop no-copy
    contract; asserted by buffer-pointer identity in tests/test_kv_quant.py
    on backends that support donation).
    """

    def ragged_step(params, state, tokens, slot, q_pos, seq_idx, valid,
                    logit_idx):
        logits, state = M.ragged_step(params, cfg, state, tokens, slot,
                                      q_pos, seq_idx, valid, logit_idx,
                                      width=width, flash_decode=flash_decode)
        return logits, jnp.argmax(logits, -1).astype(jnp.int32), state

    return _named(ragged_step, SERVE_STEP)


def make_slot_reset(cfg: ModelCfg):
    """Admission's reset: ``f(state, template, mask, rows, plen) -> state``
    giving each masked slot its block-table row and the positions it
    inherits (see ``models.model.reset_paged_slots``).  Jit with
    ``donate_argnums=(0,)``."""
    def slot_reset(state, template, mask, rows, plen):
        return M.reset_paged_slots(cfg, state, template, mask, rows, plen)

    return _named(slot_reset, SLOT_RESET)


def make_page_copy(cfg: ModelCfg):
    """Copy-on-write: ``f(state, src, dst) -> state`` copying pool pages
    ``src`` to ``dst`` (see ``models.model.copy_kv_pages``).  Jit with
    ``donate_argnums=(0,)``."""
    def page_copy(state, src, dst):
        return M.copy_kv_pages(cfg, state, src, dst)

    return _named(page_copy, PAGE_COPY)


def make_page_gather(cfg: ModelCfg):
    """Demotion mover: ``f(state, page) -> {path: rows}`` pulling one pool
    page's K/V values and int8 scale rows out of every paged leaf (see
    ``models.model.gather_kv_page``).  Jit WITHOUT donation — the state
    stays live; the engine materializes the result into host RAM."""
    def page_gather(state, page):
        return M.gather_kv_page(cfg, state, page)

    return _named(page_gather, PAGE_GATHER)


def make_page_insert(cfg: ModelCfg):
    """Promotion mover: ``f(state, page_data, page) -> state`` scattering a
    demoted page's rows back into the pools at device page ``page``.  Jit
    with ``donate_argnums=(0,)`` so the pools update in place; the engine
    issues it at admission and lets async dispatch overlap the copy with
    the tick's compute (see ``models.model.insert_kv_page``)."""
    def page_insert(state, page_data, page):
        return M.insert_kv_page(cfg, state, page_data, page)

    return _named(page_insert, PAGE_INSERT)


def make_spec_rollback(cfg: ModelCfg):
    """Speculative-rejection mover: ``f(state, mask, new_len) -> state``
    invalidating every masked slot's KV rows at positions >= new_len
    (kpos -> -1, slen clamped; pools/scales/ptab untouched — see
    ``models.model.rollback_paged_slots``).  Jit with
    ``donate_argnums=(0,)``; the engine dispatches it only on ticks that
    rejected a draft tail, and it traces once for the engine's lifetime
    like every other control-plane program."""
    def spec_rollback(state, mask, new_len):
        return M.rollback_paged_slots(cfg, state, mask, new_len)

    return _named(spec_rollback, SPEC_ROLLBACK)


# leaf name -> logical axes for decode-state leaves (unstacked; a scanned
# stage adds a leading "layer" dim)
STATE_AXES: Dict[str, tuple] = {
    # attention KV cache (lock-step engine)
    "k": ("act_kv_batch", "act_kv_seq", "act_kv_heads", None),
    "v": ("act_kv_batch", "act_kv_seq", "act_kv_heads", None),
    "k_pos": ("act_kv_seq",),
    "pos": (),
    # paged KV (per-slot engine): head-major page pools (n_pages, kvH,
    # page, hd) shard over KV heads; block tables / positions are per-slot
    # and follow the batch axis.  int8 pools add per-entry scale pools
    # (ks/vs, (n_pages, kvH, 1, page)) that shard with their pages.
    "kp": (None, "act_kv_heads", None, None),
    "vp": (None, "act_kv_heads", None, None),
    "ks": (None, "act_kv_heads", None, None),
    "vs": (None, "act_kv_heads", None, None),
    "ptab": ("act_kv_batch", None),
    "kpos": ("act_kv_batch", None),
    "slen": ("act_kv_batch",),
    # mamba
    "h": ("act_kv_batch", "tensor", None),
    "conv": ("act_kv_batch", None, "tensor"),
    # mlstm (matrix memory replicated over 'model'; it is small)
    "C": ("act_kv_batch", None, None, None),
    "n": ("act_kv_batch", None, None),
    "m": ("act_kv_batch", None),
    # slstm
    "sh": ("act_kv_batch", None),
    "sc": ("act_kv_batch", None),
    "sn": ("act_kv_batch", None),
    "sm": ("act_kv_batch", None),
}


def _state_leaf_spec(path, leaf, rules, mesh=None):
    name = None
    for p in reversed(path):
        if isinstance(p, jax.tree_util.DictKey):
            name = p.key
            break
    if name not in STATE_AXES:
        raise ValueError(f"no sharding rule for decode-state leaf {path}")
    axes = STATE_AXES[name]
    if len(leaf.shape) == len(axes) + 1:
        axes = ("layer",) + axes
    elif len(leaf.shape) != len(axes):
        raise ValueError(f"state leaf {name}: ndim {len(leaf.shape)} vs rule {len(axes)}")
    spec = logical_spec(axes, rules)
    if mesh is not None:
        from repro.parallel.sharding import sanitize_spec

        spec = sanitize_spec(spec, leaf.shape, mesh)
    return spec


def decode_state_specs(state_shapes, rules=None):
    """PartitionSpec tree for an init_decode_state() pytree."""
    from repro.parallel.sharding import current_mesh, current_rules

    rules = rules if rules is not None else current_rules()
    mesh = current_mesh()
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _state_leaf_spec(path, leaf, rules, mesh), state_shapes)
