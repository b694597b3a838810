"""Small copies of the benchmark's cells, for driving whole runs on the CPU
in tests: the same files and harness, at smoke widths and short lengths."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import spec

HERE = Path(__file__).resolve().parent

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, vocab_size=512)


def make(root: Path, cell: str, chips: int = 1) -> str:
    """Copy the benchmark under ``root`` with a small twin of ``cell``
    (same traffic kind, same checks); returns the twin's name."""
    shutil.copytree(HERE, root / HERE.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    src = spec.load(cell)
    w, c = src.workload, src.config_entry
    bdir = root / HERE.name
    hf = json.loads((HERE.parent / c["file"]).read_text())
    hf.update(TINY, name="tiny", num_key_value_heads=min(
        hf["num_key_value_heads"], TINY["num_attention_heads"]))
    (bdir / "configs" / "tiny.json").write_text(json.dumps(hf))
    s = json.loads((bdir / "cells" / f"{cell}.json").read_text())
    s.pop("prepared", None)
    s["engine"].update(batch_size=4, cache_len=256, token_budget=32,
                       prefill_chunk=8)
    s["warmup_s"] = min(s.get("warmup_s", 0.0), 2.0)
    s["check"]["sample_tokens"] = 40
    t = json.loads((bdir / "traffic" / f"{w['traffic']}.json").read_text())
    if t["kind"] == "sessions":
        t["system_prompts"]["len"] = {"dist": "uniform", "min": 20, "max": 60}
        t["user_len"].update(min=4, max=40, median=10)
        t["answer_len"].update(min=4, max=20, median=10)
        t["arrivals"].update(rate_per_s=2.0, backlog_s=2.0)
        t["think_s"].update(mean=0.5, max=2.0)
    else:
        t.update(clients=4, prompt_len={"dist": "uniform", "min": 20, "max": 90},
                 answer_len={"dist": "uniform", "min": 8, "max": 40})
    name = f"tiny.{cell.split('.', 1)[1]}"
    (bdir / "traffic" / f"tiny-{w['traffic']}.json").write_text(json.dumps(t))
    (bdir / "cells" / f"{name}.json").write_text(json.dumps(s))
    b["configs"].append(dict(c, name="tiny", file=f"{HERE.name}/configs/tiny.json"))
    b["workloads"].append(dict(w, name=name, config="tiny", chips=chips,
                               traffic=f"tiny-{w['traffic']}"))
    for m in b["end_to_end"] + b["per_layer"]:
        if cell in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return name
