"""The system under test, as the benchmark drives it.

Builds the program's model configuration from a configuration file of the
benchmark (the published ``config.json`` numbers, plus ``registry``: the
program's entry for that architecture, whose block structure is kept and
whose numbers are all replaced by the file's), and the ``ServeEngine``
from a cell's engine settings.  Only the engine's public surface is used
afterwards: ``submit``, ``tick``, ``RequestHandle.request.out_tokens``,
``stats`` and ``drop_prefix_cache``.
"""
from __future__ import annotations

import dataclasses

import jax


def model_cfg(hf: dict):
    """The program's ``ModelCfg`` holding the file's numbers."""
    from repro.configs import get_config

    base = get_config(hf["registry"])
    if len(base.stages) != 1 or len(base.stages[0].pattern) != 1:
        raise ValueError(f"{hf['registry']}: not a one-block dense stack")
    blk = base.stages[0].pattern[0]
    d, H = hf["hidden_size"], hf["num_attention_heads"]
    attn = dataclasses.replace(
        blk.attn, num_heads=H, num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim", d // H), rope_theta=float(hf["rope_theta"]),
        qkv_bias=True, window=None)
    mlp = dataclasses.replace(blk.mlp, d_ff=hf["intermediate_size"],
                              act=hf["hidden_act"], gated=True)
    stage = dataclasses.replace(
        base.stages[0], pattern=(dataclasses.replace(blk, attn=attn, mlp=mlp),),
        repeats=hf["num_hidden_layers"])
    return base.replace(
        name=hf["name"], d_model=d, vocab_size=hf["vocab_size"],
        stages=(stage,), tie_embeddings=bool(hf["tie_word_embeddings"]),
        norm_eps=float(hf["rms_norm_eps"]), param_dtype=hf["torch_dtype"],
        dtype=hf["torch_dtype"], max_seq_len=hf["max_position_embeddings"])


def check_layout(cfg, weights) -> None:
    """Fail loudly where the benchmark's weights no longer match the tree
    the program's own initializer describes (shapes only, nothing run)."""
    from repro.models import model as M

    want = jax.eval_shape(lambda k: M.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), weights)
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    if got != want:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} != {want}")


def make_mesh(chips: int):
    """KV-head tensor-parallel mesh over the first ``chips`` devices, or
    None on one chip."""
    if chips == 1:
        return None
    from repro.launch.mesh import make_mesh as mk

    return mk((chips,), ("model",))


def make_engine(weights, cfg, engine: dict, mesh):
    """``ServeEngine`` with the cell's settings passed unchanged."""
    from repro.serve.engine import ServeEngine

    return ServeEngine(weights, cfg, mesh=mesh, **engine)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (its directory is fixed
    inside the checkout unless ``JAX_COMPILATION_CACHE_DIR`` names one),
    with every program cached however short its compile."""
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
