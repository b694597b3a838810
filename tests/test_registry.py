"""The registry holds the published numbers the benchmark's configuration
files state.

The benchmark builds its model from the registry entry a file names and
then overrides the shape from the file (``bench/program.model_cfg``), so a
registry value that has drifted from the published one would never show
there; everything else that builds the model from the registry would run
the drifted value.
"""
import json
from pathlib import Path

import pytest

from repro.configs import get_config

CONFIGS = sorted((Path(__file__).resolve().parents[1] / "bench" / "configs")
                 .glob("*.json"))


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_registry_entry_matches_the_published_config(path):
    hf = json.loads(path.read_text())
    cfg = get_config(hf["registry"])
    [stage] = cfg.stages
    [blk] = stage.pattern
    a = blk.attn
    d, H = hf["hidden_size"], hf["num_attention_heads"]
    assert cfg.d_model == d
    assert stage.repeats == hf["num_hidden_layers"]
    assert a.num_heads == H
    assert a.num_kv_heads == hf["num_key_value_heads"]
    assert a.head_dim == hf.get("head_dim", d // H)
    assert blk.mlp.d_ff == hf["intermediate_size"]
    assert cfg.vocab_size == hf["vocab_size"]
    assert cfg.tie_embeddings == hf["tie_word_embeddings"]
    assert a.rope_theta == float(hf["rope_theta"])


def test_every_benchmark_configuration_is_checked():
    assert {p.stem for p in CONFIGS} >= {"qwen2-1.5b", "qwen1.5-4b"}
