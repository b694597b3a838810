"""servelint — repo-specific AST lint for the serving stack.

The serving engine's performance story rests on discipline the type system
cannot see: exactly one serve-path trace, page ids passed as DATA, no
wall-clock or RNG in the tick loop, no silently-swallowed failures.  These
rules encode that discipline statically, so a PR that breaks it fails CI
before a single tick runs.

Rules (scopes in parentheses; paths are relative to ``src/repro``):

- ``jit-outside-factory`` (all of src): a ``jax.jit(...)`` call anywhere but
  the registered factory sites (``JIT_FACTORY_SITES``) or a decorator
  position.  A stray jit in the tick path is a per-call retrace machine;
  new program factories must be registered here ON PURPOSE, which is the
  review hook.
- ``hot-nondeterminism`` (serve/, kernels/): ``np.random.*``, wall-clock
  ``time.*`` reads, or iteration over an unordered set in the serve/kernel
  hot paths.  Allowlisted: the two seeded ``default_rng((seed, ...))``
  sites in ``engine.py``/``chaos.py`` — tuple-keyed, deterministic by
  construction (the packing-invariant sampling and chaos-schedule
  contracts depend on exactly that form).  Order-insensitive reducers over
  sets (``sum``/``min``/``max``/``len``/``all``/``any``/``sorted``) pass.
- ``broad-except`` (all of src): bare ``except:`` or ``except Exception/
  BaseException``.  Intentional catch-alls (autotune candidate sweeps,
  dry-run cell loops) carry a reasoned inline suppression instead.
- ``mutable-default`` (all of src): mutable default arguments.
- ``retrace-bomb`` (serve/): a registered jitted program
  (``JITTED_PROGRAM_ATTRS``) invoked with a Python-scalar argument — an
  int/float literal, ``int()``/``float()``/``len()`` call, or arithmetic
  over those — which jit treats as a compile-time constant and retraces
  for every new value.  The page movers additionally require their page-id
  argument wrapped as array data (``np.int32(page)`` — the "page id as
  DATA" rule from ``serve_step.py``).

The analysis package itself is excluded from scanning (it builds jits and
transition tables as part of CHECKING them, not serving).
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import List, Optional, Set, Tuple

from repro.analysis.findings import Finding, Suppressions

__all__ = ["lint_file", "lint_tree", "JIT_FACTORY_SITES",
           "JITTED_PROGRAM_ATTRS"]

# (path relative to src/repro, enclosing function name) pairs where calling
# jax.jit is legitimate: the engine/reference/train constructors (programs
# built once per object) and the offline lowering helpers.  Everything else
# is a finding — add a pair here deliberately when introducing a factory.
JIT_FACTORY_SITES: Set[Tuple[str, str]] = {
    ("serve/engine.py", "__init__"),
    ("serve/reference.py", "__init__"),
    ("train/loop.py", "__init__"),
    ("core/sweep.py", "lower_cell"),
    ("core/sweep.py", "measured_gflops"),
    ("launch/dryrun.py", "_lower_train"),
    ("launch/dryrun.py", "_lower_prefill"),
    ("launch/dryrun.py", "_lower_decode"),
}

# the engine's compiled-program attributes: calls to these are the jitted
# hot path, so their arguments must be arrays (or pytrees of arrays), never
# fresh Python scalars
JITTED_PROGRAM_ATTRS: Set[str] = {
    "_ragged_step", "_reset", "_copy", "_gather_page", "_insert_page",
    "_spec_rollback", "_decode",
}
# movers whose trailing page-id argument must be wrapped as array data
_PAGE_ARG_MOVERS = {"_gather_page", "_insert_page"}

_HOT_SCOPES = ("serve/", "kernels/")
_RNG_ALLOWLIST_FILES = {"serve/engine.py", "serve/chaos.py"}
_TIME_ATTRS = {"time", "perf_counter", "monotonic", "time_ns",
               "process_time"}
_ORDER_FREE_REDUCERS = {"sum", "len", "min", "max", "all", "any", "sorted",
                        "set", "frozenset", "sorted"}
_ARRAY_NAMESPACES = {"np", "jnp", "numpy"}


def _attr_chain(node: ast.AST) -> List[str]:
    """['np', 'random', 'default_rng'] for np.random.default_rng, else []"""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


def _is_py_scalar(node: ast.AST) -> bool:
    """Expression that jit would treat as a fresh Python scalar constant."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float)) \
            and not isinstance(node.value, bool)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        return node.func.id in ("int", "float", "len")
    if isinstance(node, ast.BinOp):
        return _is_py_scalar(node.left) or _is_py_scalar(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_py_scalar(node.operand)
    return False


def _is_set_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Set):
        return True
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in ("set", "frozenset"))


class _Linter(ast.NodeVisitor):
    def __init__(self, rel: str, source: str):
        self.rel = rel  # path relative to src/repro, posix separators
        self.hot = rel.startswith(_HOT_SCOPES)
        self.in_serve = rel.startswith("serve/")
        self.findings: List[Finding] = []
        self._func_stack: List[str] = []
        self._decorator_nodes: Set[int] = set()
        self._parents: dict = {}
        tree = ast.parse(source)
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[id(child)] = parent
        self.visit(tree)

    # -- plumbing ---------------------------------------------------------
    def _flag(self, rule: str, node: ast.AST, msg: str) -> None:
        self.findings.append(
            Finding(rule, f"src/repro/{self.rel}", node.lineno, msg))

    def _enclosing(self) -> str:
        return self._func_stack[-1] if self._func_stack else "<module>"

    # -- defs: factory scoping, decorators, mutable defaults --------------
    def _visit_def(self, node) -> None:
        for dec in node.decorator_list:
            for sub in ast.walk(dec):
                self._decorator_nodes.add(id(sub))
        args = node.args
        for default in list(args.defaults) + [
                d for d in args.kw_defaults if d is not None]:
            mutable = isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(default, ast.Call)
                and isinstance(default.func, ast.Name)
                and default.func.id in ("list", "dict", "set"))
            if mutable:
                self._flag("mutable-default", default,
                           f"mutable default argument in {node.name}() is "
                           "shared across calls; default to None and build "
                           "inside")
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_FunctionDef = _visit_def
    visit_AsyncFunctionDef = _visit_def

    # -- except handlers ---------------------------------------------------
    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        names = []
        if node.type is None:
            names = ["<bare>"]
        else:
            types = (node.type.elts if isinstance(node.type, ast.Tuple)
                     else [node.type])
            for t in types:
                chain = _attr_chain(t)
                if chain and chain[-1] in ("Exception", "BaseException"):
                    names.append(chain[-1])
        if names:
            self._flag("broad-except", node,
                       f"except {'/'.join(names)} swallows unrelated "
                       "failures; catch the specific exceptions (or "
                       "suppress with a reason if the catch-all is the "
                       "point)")
        self.generic_visit(node)

    # -- loops / comprehensions: unordered-set iteration -------------------
    def _check_set_iter(self, iter_node: ast.AST, holder: ast.AST) -> None:
        if not (self.hot and _is_set_expr(iter_node)):
            return
        # an order-insensitive reducer consuming the iteration is fine:
        # sum(1 for p in set(x) ...), sorted(set(x)), max({...})
        scan: Optional[ast.AST] = holder
        while scan is not None:
            parent = self._parents.get(id(scan))
            if isinstance(parent, ast.Call) and isinstance(
                    parent.func, ast.Name) \
                    and parent.func.id in _ORDER_FREE_REDUCERS:
                return
            if isinstance(parent, (ast.stmt, type(None))):
                break
            scan = parent
        self._flag("hot-nondeterminism", iter_node,
                   "iteration order over a set is unordered across runs; "
                   "sort first or reduce order-insensitively")

    def visit_For(self, node: ast.For) -> None:
        self._check_set_iter(node.iter, node)
        self.generic_visit(node)

    def _visit_comp(self, node) -> None:
        for gen in node.generators:
            self._check_set_iter(gen.iter, node)
        self.generic_visit(node)

    visit_ListComp = visit_SetComp = visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    # -- calls: jit factories, RNG/clock, retrace bombs --------------------
    def visit_Call(self, node: ast.Call) -> None:
        chain = _attr_chain(node.func)
        if chain == ["jax", "jit"] and id(node) not in self._decorator_nodes:
            site = (self.rel, self._enclosing())
            if site not in JIT_FACTORY_SITES:
                self._flag(
                    "jit-outside-factory", node,
                    f"jax.jit called in {self._enclosing()}() — programs "
                    "are built once at registered factory sites "
                    "(servelint.JIT_FACTORY_SITES); a jit in the run path "
                    "retraces per call")
        if self.hot:
            self._check_hot_call(node, chain)
        if self.in_serve:
            self._check_jitted_program_call(node)
        self.generic_visit(node)

    def _check_hot_call(self, node: ast.Call, chain: List[str]) -> None:
        if len(chain) >= 2 and chain[0] in ("np", "numpy") \
                and chain[1] == "random":
            allowed = (self.rel in _RNG_ALLOWLIST_FILES
                       and chain[-1] == "default_rng"
                       and len(node.args) == 1
                       and isinstance(node.args[0], ast.Tuple))
            if not allowed:
                self._flag("hot-nondeterminism", node,
                           f"{'.'.join(chain)} in a serve/kernel hot path; "
                           "only tuple-seeded default_rng((seed, ...)) in "
                           "engine.py/chaos.py is deterministic by "
                           "construction")
        elif len(chain) == 2 and chain[0] == "time" \
                and chain[1] in _TIME_ATTRS:
            self._flag("hot-nondeterminism", node,
                       f"time.{chain[1]}() in a serve/kernel hot path; "
                       "wall-clock reads must never influence control flow "
                       "(measurement-only uses carry a reasoned "
                       "suppression)")

    def _check_jitted_program_call(self, node: ast.Call) -> None:
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id == "self"
                and func.attr in JITTED_PROGRAM_ATTRS):
            return
        for i, arg in enumerate(node.args):
            if _is_py_scalar(arg):
                self._flag(
                    "retrace-bomb", arg,
                    f"self.{func.attr}(...) argument {i} is a Python "
                    "scalar: jit specializes on it and retraces per "
                    "value — pass array data (np.int32(x)) instead")
        if func.attr in _PAGE_ARG_MOVERS and node.args:
            page = node.args[-1]
            wrapped = (isinstance(page, ast.Call)
                       and bool(_attr_chain(page.func))
                       and _attr_chain(page.func)[0] in _ARRAY_NAMESPACES)
            if not wrapped:
                self._flag(
                    "retrace-bomb", page,
                    f"self.{func.attr}(...) page id must be passed as "
                    "DATA (np.int32(page)): a bare Python page id bakes "
                    "into the trace and compiles one program per page")


def lint_file(path: Path, rel: Optional[str] = None) -> List[Finding]:
    """Lint one file.  ``rel`` overrides the src/repro-relative path used
    for scoping (tests point fixture files at serve/-scoped rules)."""
    source = Path(path).read_text()
    if rel is None:
        parts = Path(path).resolve().parts
        idx = len(parts) - 1 - parts[::-1].index("repro")
        rel = "/".join(parts[idx + 1:])
    findings = _Linter(rel, source).findings
    sup = Suppressions(source)
    return [sup.apply(f) for f in findings]


def lint_tree(root: Optional[Path] = None) -> List[Finding]:
    """Lint every module under ``src/repro`` (the analysis package and its
    fixtures excluded — it constructs jits and broken tables on purpose)."""
    root = Path(root) if root else Path(__file__).resolve().parents[1]
    findings: List[Finding] = []
    for p in sorted(root.rglob("*.py")):
        rel = p.relative_to(root).as_posix()
        if rel.startswith("analysis/"):
            continue
        findings.extend(lint_file(p, rel))
    return findings
