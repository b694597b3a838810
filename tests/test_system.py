"""End-to-end behaviour tests: train → improve → checkpoint → serve."""
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeCfg
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.train.loop import TrainLoop


def test_train_then_serve_end_to_end():
    """The quickstart contract: loss falls on learnable data and the trained
    params serve deterministic greedy decodes through the engine."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    shape = ShapeCfg("e2e", 48, 8, "train")
    d = tempfile.mkdtemp()
    try:
        loop = TrainLoop(cfg, shape, lr=3e-3, total_steps=40, ckpt_dir=d,
                         save_every=20)
        hist = loop.run(30)
        assert hist[-1]["loss"] < hist[0]["loss"]

        params = loop.final_state["params"]
        engine = ServeEngine(params, cfg, batch_size=2, cache_len=64)
        prompts = [np.arange(8) % cfg.vocab_size,
                   (np.arange(8) * 5) % cfg.vocab_size]
        uids = [engine.submit(p, max_tokens=5) for p in prompts]
        results = engine.run()
        assert all(len(results[u]) == 5 for u in uids)

        # engine output == single-request reference decode
        state = M.init_decode_state(params, cfg, 1, 64)
        state = M.prefill(params, cfg, state, jnp.asarray(prompts[0])[None])
        tok = jnp.asarray([[prompts[0][-1]]], jnp.int32)
        ref = []
        for _ in range(5):
            lg, state = M.decode_step(params, cfg, state, tok)
            tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
            ref.append(int(tok[0, 0]))
        assert ref == results[uids[0]]
    finally:
        shutil.rmtree(d)


def test_moe_arch_trains():
    """An MoE arch trains without NaNs and the aux losses stay bounded."""
    cfg = get_config("arctic-480b", smoke=True)
    shape = ShapeCfg("moe", 32, 4, "train")
    hist = TrainLoop(cfg, shape, lr=1e-3, total_steps=20).run(12)
    assert np.isfinite(hist[-1]["loss"])
    assert hist[-1]["loss"] < hist[0]["loss"] * 1.2  # no divergence


def test_hybrid_arch_trains():
    cfg = get_config("jamba-1.5-large-398b", smoke=True)
    shape = ShapeCfg("hyb", 32, 4, "train")
    hist = TrainLoop(cfg, shape, lr=1e-3, total_steps=20).run(8)
    assert np.isfinite(hist[-1]["loss"])


def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path):
    """The launch entry points keep JAX's persistent compile cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, setting nothing in code, and
    otherwise at one fixed, git-ignored path inside the checkout."""
    from pathlib import Path

    from repro.launch.compile_cache import enable_compile_cache

    repo = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
