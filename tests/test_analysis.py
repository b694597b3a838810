"""Tier-1 gate for repro.analysis: per-rule fixtures with exact file:line
assertions, clean-run over the real tree, the donation proof, the
lifecycle model checker (incl. seeded-broken tables), scheduler protocol
conformance, and a CLI smoke — so ``pytest -x -q`` gates the analyzer the
same way CI's ``python -m repro.analysis --fail-on-findings`` does."""
import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import run_all
from repro.analysis.findings import Finding, Suppressions, load_baseline
from repro.analysis.servelint import lint_file, lint_tree

FIXTURES = Path(__file__).parent / "fixtures" / "servelint"


def _lint_fixture(name: str):
    """Lint a fixture as if it lived in serve/ (hot-path rules active)."""
    return lint_file(FIXTURES / name, rel=f"serve/{name}")


def _keys(findings, only_rule=None):
    return sorted((f.rule, f.line) for f in findings
                  if not f.suppressed and (only_rule is None
                                           or f.rule == only_rule))


# ---------------------------------------------------------------------------
# servelint: one fixture per rule, exact line/rule-id assertions


def test_jit_outside_factory_fixture():
    got = _lint_fixture("jit_outside_factory.py")
    assert _keys(got) == [("jit-outside-factory", 10)]


def test_hot_nondeterminism_fixture():
    got = _lint_fixture("hot_nondeterminism.py")
    assert _keys(got) == [("hot-nondeterminism", 11),
                          ("hot-nondeterminism", 12),
                          ("hot-nondeterminism", 13),
                          ("hot-nondeterminism", 16)]


def test_hot_rules_scope_to_hot_paths():
    # the same file linted OUTSIDE serve//kernels/: hot rules are off
    got = lint_file(FIXTURES / "hot_nondeterminism.py",
                    rel="core/hot_nondeterminism.py")
    assert _keys(got, "hot-nondeterminism") == []


def test_broad_except_fixture():
    got = _lint_fixture("broad_except.py")
    assert _keys(got) == [("broad-except", 10), ("broad-except", 17)]


def test_mutable_default_fixture():
    got = _lint_fixture("mutable_default.py")
    assert _keys(got) == [("mutable-default", 7), ("mutable-default", 12)]


def test_retrace_bomb_fixture():
    got = _lint_fixture("retrace_bomb.py")
    assert _keys(got) == [("retrace-bomb", 10), ("retrace-bomb", 11)]


def test_suppression_fixture():
    got = _lint_fixture("suppressed.py")
    sup = [f for f in got if f.suppressed]
    # both perf_counter hits suppressed (same-line and own-line-above)
    assert sorted(f.line for f in sup) == [9, 12]
    assert all(f.rule == "hot-nondeterminism" for f in sup)
    assert "measurement-only fixture" in sup[0].reason
    # a suppression naming the WRONG rule does not cover the broad except
    assert _keys(got) == [("broad-except", 19)]


def test_suppression_requires_named_rule():
    sup = Suppressions("x = 1  # servelint: ignore[other-rule] — nope\n")
    assert sup.lookup(1, "broad-except") == (False, "")
    hit, reason = sup.lookup(1, "other-rule")
    assert hit and reason == "nope"


# ---------------------------------------------------------------------------
# clean run + baseline: the real tree must have zero actionable findings


def test_real_tree_is_clean():
    unsuppressed = [f for f in lint_tree() if not f.suppressed]
    assert unsuppressed == [], \
        "\n".join(str(f) for f in unsuppressed)


def test_baseline_is_empty():
    assert load_baseline() == set(), \
        "baseline.json must stay empty: fix or inline-suppress findings"


def test_suppressions_carry_reasons():
    tolerated = [f for f in lint_tree() if f.suppressed]
    assert tolerated, "expected the documented intentional catch-alls"
    for f in tolerated:
        assert f.reason, f"suppression without a reason: {f}"


# ---------------------------------------------------------------------------
# contracts: the donation proof over the real serve programs


def test_donation_contract_static_proof():
    from repro.analysis.contracts import SERVE_PROGRAMS, check_contracts

    findings, stats = check_contracts(compile_programs=True)
    assert [str(f) for f in findings] == []
    progs = stats["programs"]
    assert set(progs) == set(SERVE_PROGRAMS)
    for name, rec in progs.items():
        assert rec["proved"], (name, rec)
        assert rec["donated_leaves"] == 4  # kp/vp + int8 ks/vs
    # the gather keeps its state LIVE: nothing aliased at all
    assert progs["_gather_page"]["aliased_params"] == 0
    assert all(progs[n]["aliased_params"] > 0 for n in progs
               if n != "_gather_page")


def test_donation_ast_layers_catch_drift(tmp_path):
    # rewriting the engine source with a missing donation must be caught by
    # the AST cross-check layer (compile_programs=False path)
    from repro.analysis import contracts

    src = contracts._ENGINE_PATH.read_text()
    broken = src.replace(
        "self._reset = jax.jit(make_slot_reset(cfg), donate_argnums=(0,))",
        "self._reset = jax.jit(make_slot_reset(cfg))")
    assert broken != src
    bad = tmp_path / "engine.py"
    bad.write_text(broken)
    orig = contracts._ENGINE_PATH
    try:
        contracts._ENGINE_PATH = bad
        findings, _ = contracts.check_contracts(compile_programs=False)
    finally:
        contracts._ENGINE_PATH = orig
    assert any(f.rule == "donation-contract" and "_reset" in f.message
               for f in findings)


def test_assert_donated_rejects_partial():
    from repro.analysis.contracts import assert_donated

    class FakeLeaf:
        def __init__(self, ptr):
            self._ptr = ptr

        def unsafe_buffer_pointer(self):
            return self._ptr

    before = {"['kp']": 1, "['vp']": 2}
    with pytest.raises(AssertionError, match="partially donated"):
        assert_donated(before, {"kp": FakeLeaf(1), "vp": FakeLeaf(99)})
    assert assert_donated(before, {"kp": FakeLeaf(1),
                                   "vp": FakeLeaf(2)}) == "donated"
    assert assert_donated(before, {"kp": FakeLeaf(7),
                                   "vp": FakeLeaf(8)}) == "undonated"


# ---------------------------------------------------------------------------
# lifecycle: exhaustive pass on the real table, counterexamples on broken


def test_lifecycle_exhaustive_pass():
    from repro.analysis.lifecycle import check_lifecycle

    res = check_lifecycle()
    assert res.ok, res.violations
    assert res.states_explored > 50  # genuinely explored, not vacuous
    assert res.states_explored < 200_000  # full closure, not truncated


@pytest.mark.parametrize("breakage,invariant", [
    ("storm-drops-parks", "parked-pinned"),
    ("release-leaks", "conservation"),
    ("double-free", "conservation"),
])
def test_lifecycle_broken_tables_caught(breakage, invariant):
    from repro.analysis.lifecycle import broken_model, check_lifecycle

    res = check_lifecycle(broken_model(breakage))
    assert not res.ok
    names = [inv for inv, _, _ in res.violations]
    assert invariant in names
    # the counterexample trace is replayable: non-empty op sequence
    trace = next(tr for inv, _, tr in res.violations if inv == invariant)
    assert trace, "BFS must return the shortest witnessing op sequence"


# ---------------------------------------------------------------------------
# protocols: the live registry conforms; a broken policy is caught


def test_scheduler_registry_conforms():
    from repro.analysis.protocols import check_protocols

    findings, stats = check_protocols()
    assert [str(f) for f in findings] == []
    assert set(stats["schedulers"]) >= {"fifo", "slo", "speculative"}


def test_broken_scheduler_caught():
    from repro.analysis.protocols import _check_instance
    from repro.serve.scheduler import Scheduler

    class DoubleAdmit(Scheduler):
        def admission_order(self, view):
            return [0, 0] if view.queue else []

    class SlotDropper(Scheduler):
        def decode_order(self, view, ready):
            return list(ready)[:-1]

    assert any("duplicate" in f.message for f in _check_instance(
        "dup", DoubleAdmit(), "x.py", 1))
    assert any("PERMUTE" in f.message for f in _check_instance(
        "drop", SlotDropper(), "x.py", 1))


def test_nondelegating_wrapper_caught(tmp_path):
    from repro.analysis.protocols import _check_wrapper_delegation

    src = textwrap.dedent("""
        class SneakyWrapper:
            def __init__(self, inner):
                self.inner = inner

            def admission_order(self, view):
                return self.inner.admission_order(view)

            def decode_order(self, view, ready):
                return list(reversed(self.inner.decode_order(view, ready)))
    """)
    p = tmp_path / "sched.py"
    p.write_text(src)
    findings = _check_wrapper_delegation("sched.py", p)
    assert len(findings) == 1
    assert "decode_order" in findings[0].message
    assert "VERBATIM" in findings[0].message


# ---------------------------------------------------------------------------
# CLI: the CI entrypoint, in-process


def test_cli_clean_run(tmp_path, capsys):
    from repro.analysis.__main__ import main

    out = tmp_path / "findings.json"
    rc = main(["--fail-on-findings", "--passes", "lint,lifecycle,protocols",
               "--json", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "0 actionable" in text
    dumped = json.loads(out.read_text())
    assert all(f["suppressed"] for f in dumped)


def test_cli_rejects_unknown_pass():
    with pytest.raises(ValueError, match="unknown passes"):
        run_all(["nope"])


def test_full_gate():
    """Exactly what CI runs: every pass, fail on any actionable finding."""
    from repro.analysis.__main__ import main

    assert main(["--fail-on-findings"]) == 0
