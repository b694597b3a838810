"""The harness finds configurations, traffic mixes, cells and metrics by
name in files of their own, so a later cell or metric needs new files and
new entries only."""
from __future__ import annotations

import hashlib
import json
import re
import shutil
from pathlib import Path

import pytest

import spec

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        c = spec.load(w["name"])
        assert c.reference.is_file()
        assert c.config["name"] == w["config"]
        assert c.end_to_end and c.per_layer
        assert any(m["name"] == "setup_s" for m in c.end_to_end)
        for m in c.end_to_end + c.per_layer:
            assert callable(spec.reader(m["name"]))


def test_names_and_cross_references_hold():
    cells = {w["name"] for w in BENCH["workloads"]}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for x in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(x["name"]), x["name"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for c in m["workloads"]:
            assert c in cells
            assert c in e2e[m["moves"]].get("workloads", [c])
    for m in metrics:
        assert set(m.get("workloads", [])) <= cells
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_a_new_cell_and_metric_are_new_files_and_entries(tmp_path):
    shutil.copytree(HERE, tmp_path / HERE.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = _digest(tmp_path)
    # a new traffic mix, cell and per-layer metric: new files ...
    tdir = tmp_path / HERE.name
    chat = json.loads((tdir / "traffic" / "chat.json").read_text())
    chat["arrivals"]["rate_per_s"] *= 1.5
    (tdir / "traffic" / "chat-overload.json").write_text(json.dumps(chat))
    (tdir / "cells" / "qwen2-1.5b.chat-overload.json").write_text(
        (tdir / "cells" / "qwen2-1.5b.chat.json").read_text())
    (tdir / "metrics" / "queue_depth.py").write_text(
        "def read(run):\n    return 7.0\n")
    # ... and new entries
    bench["workloads"].append({"name": "qwen2-1.5b.chat-overload",
                               "config": "qwen2-1.5b",
                               "traffic": "chat-overload", "chips": 1,
                               "why": "chat above the knee"})
    bench["end_to_end"].append({"name": "done_tok_s", "unit": "tokens/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["qwen2-1.5b.chat-overload"]})
    bench["per_layer"].append({"name": "queue_depth.overload", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "engine host loop",
                               "moves": "done_tok_s",
                               "workloads": ["qwen2-1.5b.chat-overload"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.load("qwen2-1.5b.chat-overload", tmp_path)
    assert c.traffic["arrivals"]["rate_per_s"] == pytest.approx(
        1.5 * json.loads((HERE / "traffic" / "chat.json").read_text())
        ["arrivals"]["rate_per_s"])
    assert [m["name"] for m in c.per_layer] == ["queue_depth.overload"]
    assert spec.reader("queue_depth.overload", tmp_path)(None) == 7.0
    # a metric split by cell shares its quantity's reader
    assert spec.reader("step_ms.overload", tmp_path) is not None
    # no file that was there has changed
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        spec.load("no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric")
