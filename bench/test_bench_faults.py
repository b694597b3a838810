"""Whole runs of small twins of the cells on the CPU, past the look for a
chip: a sound run comes out correct, and a run with the timed path broken
underneath comes out not correct, once for each fault a serving cell can
have: a step that leaves its state unchanged (no K/V written), a token
altered where it is produced, and, for the tensor-parallel cell, the
exchange between chips left out (every chip's attention output but the
first chip's heads lost before the output projection)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

import harness
import smallcell
import spec
from repro.models.layers import attention
from repro.serve.engine import ServeEngine

SEED = 2 ** 31 + 1234567


def run(tmp_path, cell, seconds=5.0):
    name = smallcell.make(tmp_path, cell)
    c = spec.load(name, tmp_path)
    return harness.run_cell(c, SEED, seconds, False, jax.devices()[:1],
                            time.perf_counter())


@pytest.fixture(params=["qwen2-1.5b.chat", "qwen1.5-4b.tp4-decode"])
def cell(request):
    return request.param


def test_sound_run_is_correct(tmp_path, cell):
    out = run(tmp_path, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_state_left_unchanged_is_caught(tmp_path, monkeypatch, cell):
    monkeypatch.setattr(attention, "_scatter_paged_kv",
                        lambda cache, *a, **k: None)
    out = run(tmp_path, cell)
    assert not out["correct"], out["checks"]


def test_altered_token_is_caught(tmp_path, monkeypatch, cell):
    sample = ServeEngine._sample

    def altered(self, req, row, ordinal):
        tok = sample(self, req, row, ordinal)
        return (tok + 1) % row.shape[-1] if ordinal == 3 else tok

    monkeypatch.setattr(ServeEngine, "_sample", altered)
    out = run(tmp_path, cell)
    assert not out["correct"], out["checks"]


def test_exchange_left_out_is_caught(tmp_path, monkeypatch):
    proj = attention._out_proj_replicated

    def first_chip_only(params, cfg, o):
        keep = jnp.arange(o.shape[-3]) < o.shape[-3] // 4
        return proj(params, cfg, jnp.where(keep[:, None, None], o, 0))

    monkeypatch.setattr(attention, "_out_proj_replicated", first_chip_only)
    out = run(tmp_path, "qwen1.5-4b.tp4-decode")
    assert not out["correct"], out["checks"]
