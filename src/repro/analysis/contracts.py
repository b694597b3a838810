"""Donation contract checker: a static PROOF that the serve programs
update the KV pools in place.

The engine's hot-loop no-copy contract — every tick's new KV entries
scatter into the existing page pools instead of copying them — has been
enforced dynamically since PR 4 by a buffer-pointer identity assert that a
test must happen to execute.  This module turns it into a static property
of the compiled artifacts: each serve program the engine builds in
``ServeEngine.__init__`` is lowered and compiled on SHAPE-ONLY dummies
(``jax.jit(...).lower(...).compile()`` — no weights, no execution), and the
executable's ``input_output_alias`` table is checked to actually donate
every pool leaf (``POOL_LEAVES``: kp/vp value pools and ks/vs int8 scale
pools) of the donated state argument — while ``make_page_gather``, whose
state must stay LIVE (the engine reads the gathered rows out to host RAM
afterwards), is proven to alias nothing.

Three layers of checking, strongest last:

1. **Alias feasibility** (abstract eval): every pool leaf of the donated
   input has a same-shape/same-dtype twin in the output — the necessary
   condition for XLA to alias them.
2. **Compiled aliasing** (the proof): the executable's input_output_alias
   table maps every pool-leaf parameter of the donated argument to an
   output — XLA will reuse those buffers, so the pools can never be
   copied by this program.
3. **Engine source cross-check** (AST): ``ServeEngine.__init__`` really
   jits each program with the registered donation signature (so the
   checked programs are the shipped ones, not lookalikes), and at every
   call site of a donated program the donated state variable is REBOUND by
   the call's own assignment — a donated buffer is invalid after the call,
   and rebinding is the static guarantee nothing reads it post-call.

``assert_donated`` / ``pool_buffer_pointers`` are the shared RUNTIME form
of the same contract (used by tests/test_kv_quant.py), kept here so the
dynamic assert and the static checker read the same ``POOL_LEAVES`` list
and cannot drift.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.models.layers.attention import POOL_LEAVES

__all__ = ["POOL_LEAVES", "pool_buffer_pointers", "assert_donated",
           "check_contracts", "SERVE_PROGRAMS"]

_RULE = "donation-contract"


# ---------------------------------------------------------------------------
# Runtime form (shared with tests): buffer-pointer identity


def _leaf_name(path) -> Optional[str]:
    for p in reversed(path):
        if isinstance(p, jax.tree_util.DictKey):
            return p.key
    return None


def pool_buffer_pointers(state) -> Optional[Dict[str, int]]:
    """{tree path: device buffer pointer} for every pool leaf, or None when
    the backend exposes no buffer pointers (donation untestable there)."""
    ptrs: Dict[str, int] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if _leaf_name(path) in POOL_LEAVES:
            try:
                ptrs[jax.tree_util.keystr(path)] = leaf.unsafe_buffer_pointer()
            except Exception:  # backend-specific error types
                return None
    return ptrs


def assert_donated(before: Dict[str, int], state) -> str:
    """Runtime no-copy check: ``before`` is a ``pool_buffer_pointers``
    snapshot taken pre-call, ``state`` the post-call pytree.  Returns
    "donated" when every pool buffer was updated in place, "undonated"
    when the backend donated nothing (tolerated — some backends can't),
    and raises AssertionError on a PARTIAL donation, which is always a
    bug: some pools copied while others aliased."""
    after: Dict[str, int] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        key = jax.tree_util.keystr(path)
        if key in before:
            after[key] = leaf.unsafe_buffer_pointer()
    missing = sorted(set(before) - set(after))
    assert not missing, f"pool leaves vanished from the state: {missing}"
    if after == before:
        return "donated"
    assert all(after[k] != before[k] for k in before), (
        "pools partially donated: some copied, some aliased — "
        f"{ {k: (before[k], after[k]) for k in before} }")
    return "undonated"


# ---------------------------------------------------------------------------
# Static form: lower + compile on shape dummies, prove input_output_alias


def _smoke_setup():
    """Tiny all-global-attention config + shape-only dummies mirroring
    ``ServeEngine.__init__``/``_ensure_state`` exactly (int8 pools so the
    scale-pool leaves exist; spec_k > 0 so the rollback program and the
    widened logit_idx are exercised)."""
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config("qwen2-1.5b", smoke=True).replace(dtype="float32")
    B, cache_len, page, chunk, T, spec_k = 2, 64, 8, 8, 16, 2
    pps = -(-cache_len // page)
    n_pages = B * pps
    pshapes = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    sshapes = jax.eval_shape(
        lambda p: M.init_paged_state(p, cfg, B, cache_len, page_size=page,
                                     n_pages=n_pages, window_extra=chunk,
                                     kv_dtype="int8"), pshapes)
    dims = dict(B=B, chunk=chunk, T=T, pps=pps, spec_k=spec_k)
    return cfg, pshapes, sshapes, dims


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _build_programs(cfg, pshapes, sshapes, dims):
    """(name, fn, args, donate_argnums, donated_argnum, expect_donated) for
    every program ``ServeEngine.__init__`` jits — built from the SAME
    builders and the same ``STATE_DONATE_ARGNUM`` the engine uses."""
    from repro.models import model as M
    from repro.serve.serve_step import (STATE_DONATE_ARGNUM, make_page_copy,
                                        make_page_gather, make_page_insert,
                                        make_ragged_step, make_slot_reset,
                                        make_spec_rollback)

    B, chunk, T, pps, spec_k = (dims["B"], dims["chunk"], dims["T"],
                                dims["pps"], dims["spec_k"])
    i32, b_ = jnp.int32, jnp.bool_
    page = _sds((), i32)
    page_data = jax.eval_shape(
        lambda s, p: M.gather_kv_page(cfg, s, p), sshapes, page)
    donate = (STATE_DONATE_ARGNUM,)
    return [
        ("_ragged_step",
         make_ragged_step(cfg, width=max(chunk + 1, 1 + spec_k)),
         (pshapes, sshapes, _sds((T,), i32), _sds((T,), i32),
          _sds((T,), i32), _sds((T,), i32), _sds((T,), b_),
          _sds((B, 1 + spec_k), i32)),
         donate, STATE_DONATE_ARGNUM, True),
        ("_reset", make_slot_reset(cfg),
         (sshapes, sshapes, _sds((B,), b_), _sds((B, pps), i32),
          _sds((B,), i32)),
         (0,), 0, True),
        ("_copy", make_page_copy(cfg),
         (sshapes, _sds((B,), i32), _sds((B,), i32)),
         (0,), 0, True),
        ("_gather_page", make_page_gather(cfg), (sshapes, page),
         (), 0, False),
        ("_insert_page", make_page_insert(cfg), (sshapes, page_data, page),
         (0,), 0, True),
        ("_spec_rollback", make_spec_rollback(cfg),
         (sshapes, _sds((B,), b_), _sds((B,), i32)),
         (0,), 0, True),
    ]


def _pool_leaf_indices(args: tuple, argnum: int) -> Dict[int, str]:
    """Flat entry-parameter index -> leaf path, for every pool leaf of
    ``args[argnum]``.  jit flattens the argument tuple leaf-by-leaf in
    order, so a leaf's position IS its XLA entry parameter number."""
    out: Dict[int, str] = {}
    for i, (path, _) in enumerate(
            jax.tree_util.tree_flatten_with_path(args)[0]):
        if not (path and isinstance(path[0], jax.tree_util.SequenceKey)
                and path[0].idx == argnum):
            continue
        if _leaf_name(path) in POOL_LEAVES:
            out[i] = jax.tree_util.keystr(path)
    return out


def _compiled_alias_params(compiled_text: str) -> Dict[int, tuple]:
    """Parse the HLO module header's ``input_output_alias={ {out}: (param,
    {index}, kind), ... }`` into {param_number: (out_index, kind)}."""
    start = compiled_text.find("input_output_alias={")
    if start < 0:
        return {}
    body, depth = [], 0
    for ch in compiled_text[start + len("input_output_alias="):]:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                break
        body.append(ch)
    table = "".join(body)
    out: Dict[int, tuple] = {}
    for m in re.finditer(
            r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[\d,\s]*\}"
            r"(?:,\s*([\w-]+))?\)", table):
        out[int(m.group(2))] = (m.group(1).strip(), m.group(3) or "")
    return out


_ENGINE_PATH = Path(__file__).resolve().parents[1] / "serve" / "engine.py"
_ENGINE_REL = "src/repro/serve/engine.py"

# what ServeEngine.__init__ must pass as donate_argnums for each program
# attribute: "donate" = the shared (STATE_DONATE_ARGNUM,) tuple, an int =
# that literal argnum, None = NO donation allowed
_EXPECTED_JIT_DONATION: Dict[str, Optional[object]] = {
    "_ragged_step": "donate", "_reset": 0, "_copy": 0,
    "_gather_page": None, "_insert_page": 0, "_spec_rollback": 0,
}
# donated-state argument position at each program's CALL sites
_DONATED_CALL_ARG: Dict[str, int] = {
    "_ragged_step": 1, "_reset": 0, "_copy": 0, "_insert_page": 0,
    "_spec_rollback": 0,
}

SERVE_PROGRAMS = tuple(_EXPECTED_JIT_DONATION)


def _check_engine_jit_construction(tree: ast.Module) -> List[Finding]:
    """The compiled-artifact proof covers programs built from the shared
    builders; this pass pins the ENGINE's own constructor to the same
    donation signatures, so the proof is about the shipped programs."""
    findings: List[Finding] = []
    seen: Dict[str, Optional[object]] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Attribute)
                and isinstance(tgt.value, ast.Name)
                and tgt.value.id == "self"
                and tgt.attr in _EXPECTED_JIT_DONATION):
            continue
        call = node.value
        if not (isinstance(call, ast.Call) and isinstance(
                call.func, ast.Attribute) and call.func.attr == "jit"):
            continue
        donate_kw = next((kw.value for kw in call.keywords
                          if kw.arg == "donate_argnums"), None)
        if donate_kw is None:
            seen[tgt.attr] = None
        elif isinstance(donate_kw, ast.Name):
            seen[tgt.attr] = donate_kw.id
        elif isinstance(donate_kw, ast.Tuple) and donate_kw.elts:
            elt = donate_kw.elts[0]
            seen[tgt.attr] = (elt.value if isinstance(elt, ast.Constant)
                              else ast.unparse(elt))
        else:
            seen[tgt.attr] = ast.unparse(donate_kw)
        if seen[tgt.attr] != _EXPECTED_JIT_DONATION[tgt.attr]:
            findings.append(Finding(
                _RULE, _ENGINE_REL, node.lineno,
                f"self.{tgt.attr} jitted with donate_argnums="
                f"{seen[tgt.attr]!r}; the registered contract requires "
                f"{_EXPECTED_JIT_DONATION[tgt.attr]!r}"))
    for attr in _EXPECTED_JIT_DONATION:
        if attr not in seen:
            findings.append(Finding(
                _RULE, _ENGINE_REL, 1,
                f"ServeEngine.__init__ no longer jits self.{attr} — update "
                "analysis.contracts if the program registry changed"))
    return findings


def _check_donated_not_read_post_call(tree: ast.Module) -> List[Finding]:
    """Every call of a donated program must REBIND its donated state
    argument in the same assignment (``state = self._reset(state, ...)``):
    the input buffer is dead after the call, and rebinding is the static
    guarantee no later statement reads it."""
    findings: List[Finding] = []
    parents: Dict[int, ast.AST] = {}
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            parents[id(child)] = parent
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "self"
                and node.func.attr in _DONATED_CALL_ARG):
            continue
        pos = _DONATED_CALL_ARG[node.func.attr]
        if pos >= len(node.args):
            continue
        donated = ast.unparse(node.args[pos])
        stmt = parents.get(id(node))
        ok = False
        if isinstance(stmt, ast.Assign) and stmt.value is node:
            targets: List[str] = []
            for t in stmt.targets:
                elts = t.elts if isinstance(t, ast.Tuple) else [t]
                targets.extend(ast.unparse(e) for e in elts)
            ok = donated in targets
        if not ok:
            findings.append(Finding(
                _RULE, _ENGINE_REL, node.lineno,
                f"self.{node.func.attr}(...) donates {donated!r} but the "
                "call does not rebind it — the donated buffer is invalid "
                "after the call and any later read is use-after-free"))
    return findings


def check_contracts(compile_programs: bool = True
                    ) -> Tuple[List[Finding], Dict]:
    """Run the donation contract checker.  Returns (findings, stats);
    stats records per-program proof results for the CLI report."""
    findings: List[Finding] = []
    stats: Dict[str, Dict] = {"programs": {}}
    tree = ast.parse(_ENGINE_PATH.read_text())
    findings.extend(_check_engine_jit_construction(tree))
    findings.extend(_check_donated_not_read_post_call(tree))
    if not compile_programs:
        return findings, stats

    cfg, pshapes, sshapes, dims = _smoke_setup()
    rel = "src/repro/serve/serve_step.py"
    for (name, fn, args, donate, argnum,
         expect) in _build_programs(cfg, pshapes, sshapes, dims):
        pool_idx = _pool_leaf_indices(args, argnum)
        record = {"donated_leaves": len(pool_idx), "expect_donated": expect}
        # 1) alias feasibility: each donated pool leaf has a same-shape/
        #    dtype twin in the output pytree
        out_shapes = jax.eval_shape(fn, *args)
        out_pools = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                out_shapes)[0]:
            if _leaf_name(path) in POOL_LEAVES:
                out_pools.setdefault((leaf.shape, str(leaf.dtype)),
                                     0)
                out_pools[(leaf.shape, str(leaf.dtype))] += 1
        if expect:
            in_pools: Dict[tuple, int] = {}
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    args[argnum])[0]:
                if _leaf_name(path) in POOL_LEAVES:
                    k = (leaf.shape, str(leaf.dtype))
                    in_pools[k] = in_pools.get(k, 0) + 1
            for k, n in in_pools.items():
                if out_pools.get(k, 0) < n:
                    findings.append(Finding(
                        _RULE, rel, 1,
                        f"{name}: {n} donated pool leaves of shape/dtype "
                        f"{k} but only {out_pools.get(k, 0)} in the "
                        "output — aliasing is infeasible, the program "
                        "must copy"))
        # 2) the proof: compile on shape dummies, read the alias table.
        #    jit DROPS unused flat arguments at lowering (e.g. the LM-head
        #    params when with_logits=False), so a leaf's XLA entry-parameter
        #    number is its rank among the KEPT flat indices, not its flat
        #    index — remap through kept_var_idx before reading the table.
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        n_flat = len(jax.tree_util.tree_flatten_with_path(args)[0])
        try:
            kept = sorted(lowered._lowering.compile_args["kept_var_idx"])
        except (AttributeError, KeyError):  # private API moved: assume none
            kept = list(range(n_flat))  # dropped (conservative: flat == entry)
        entry = {flat: kept.index(flat) for flat in pool_idx if flat in kept}
        dropped = sorted(set(pool_idx) - set(entry))
        if dropped:
            findings.append(Finding(
                _RULE, rel, 1,
                f"{name}: donated pool leaves "
                f"{[pool_idx[i] for i in dropped]} are UNUSED by the "
                "program — the donated state is not the state this "
                "program updates"))
        aliased = _compiled_alias_params(lowered.compile().as_text())
        record["aliased_params"] = len(aliased)
        if expect:
            missing = [key for i, key in sorted(pool_idx.items())
                       if entry.get(i, -1) not in aliased]
            record["proved"] = not missing
            if missing:
                findings.append(Finding(
                    _RULE, rel, 1,
                    f"{name}: compiled executable does NOT donate pool "
                    f"leaves {missing} — the hot loop would copy the "
                    "pool every call"))
        else:
            stray = sorted(pool_idx[i] for i, e in entry.items()
                           if e in aliased)
            record["proved"] = not stray
            if stray:
                findings.append(Finding(
                    _RULE, rel, 1,
                    f"{name}: compiled executable aliases state "
                    f"parameters {stray} but this program's state must "
                    "stay LIVE (the engine reads it after the call)"))
        stats["programs"][name] = record
    return findings, stats
