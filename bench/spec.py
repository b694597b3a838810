"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

- a configuration: the file its ``configs`` entry names, and the plain
  reference that file names (``bench/references/<reference>.py``);
- a traffic mix: ``bench/traffic/<traffic>.json``;
- a cell's engine settings, window and limits: ``bench/cells/<cell>.json``
  (a cell built but not yet listed carries its would-be entries there,
  under ``prepared``, for the tools and tests that run it before it is);
- a metric: the reader ``bench/metrics/<name>.py``, or, where there is
  none, ``bench/metrics/<name up to its first dot>.py``: a quantity split
  by cell (``step_ms.chat``) shares one reader.

So a later cell or metric is new files and new entries, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List, Optional

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    root: Path  # the checkout
    name: str
    chips: int
    workload: dict  # the BENCHMARK.json entry
    settings: dict  # bench/cells/<name>.json
    config: dict  # the configuration file
    config_entry: dict
    traffic: dict
    reference: Path
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: Optional[Path] = None) -> Cell:
    bench_dir = HERE if root is None else Path(root) / HERE.name
    root = HERE.parent if root is None else Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    path = bench_dir / "cells" / f"{name}.json"
    settings = json.loads(path.read_text()) if path.is_file() else {}
    if name in work:
        w = work[name]
        centry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    elif "prepared" in settings:
        w, centry = settings["prepared"]["workload"], settings["prepared"]["config"]
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    config = json.loads((root / centry["file"]).read_text())
    return Cell(
        root=root, name=name, chips=w["chips"], workload=w,
        settings=settings,
        config=config, config_entry=centry,
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        reference=bench_dir / "references" / f"{config['reference']}.py",
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric: str, root: Optional[Path] = None) -> Callable:
    """The ``read(run)`` function of a metric, found by its name."""
    mdir = (HERE if root is None else Path(root) / HERE.name) / "metrics"
    for stem in (metric, metric.split(".")[0]):
        path = mdir / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {metric!r} in {mdir}")
