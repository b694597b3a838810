"""Per-architecture smoke tests (deliverable f): a REDUCED config of each
assigned arch runs one forward + one train step on CPU, asserting output
shapes and no NaNs; decoder archs additionally run prefill + decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, skip_reason
from repro.models import model as M
from repro.optim.adamw import AdamWCfg
from repro.optim.schedules import constant
from repro.train.train_step import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)
B, S = 2, 32

# The last seed-era xfail group is gone: transformer._barrier gives
# optimization_barrier a custom JVP, so the remat-barrier archs' train
# steps differentiate and every arch gates strictly.
_TRAIN_ARCHS = list(ARCH_NAMES)


def _batch(cfg, key=KEY):
    ks = jax.random.split(key, 4)
    batch = {}
    if cfg.frontend == "audio":
        batch["feats"] = jax.random.normal(ks[0], (B, S, cfg.d_model // 2),
                                           jnp.bfloat16)
    else:
        batch["tokens"] = jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)
    if cfg.frontend == "vision":
        batch["img_feats"] = jax.random.normal(
            ks[1], (B, cfg.n_img_tokens, cfg.d_model // 2), jnp.bfloat16)
    batch["labels"] = jax.random.randint(ks[2], (B, S), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init_params(KEY, cfg)
    logits, aux = M.forward(params, cfg, _batch(cfg))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    assert np.isfinite(float(aux["moe_lb_loss"]))


@pytest.mark.parametrize("arch", _TRAIN_ARCHS)
def test_train_step_no_nans(arch):
    cfg = get_config(arch, smoke=True)
    opt = AdamWCfg()
    state = init_train_state(KEY, cfg, opt)
    step = make_train_step(cfg, opt, constant(1e-3))
    state, metrics = jax.jit(step)(state, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    for leaf in jax.tree.leaves(state["params"]):
        assert not bool(jnp.isnan(leaf).any())


@pytest.mark.parametrize("arch", [a for a in ARCH_NAMES
                                  if skip_reason(a, "decode_32k") is None])
def test_decode_smoke(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init_params(KEY, cfg)
    enc = None
    if cfg.frontend == "vision":
        enc = jax.random.normal(KEY, (B, cfg.n_img_tokens, cfg.d_model // 2),
                                jnp.bfloat16)
    state = M.init_decode_state(params, cfg, B, 64, enc_feats=enc)
    toks = jax.random.randint(KEY, (B, 8), 0, cfg.vocab_size)
    state = M.prefill(params, cfg, state, toks, enc_feats=enc)
    t = toks[:, -1:]
    for _ in range(3):
        logits, state = M.decode_step(params, cfg, state, t)
        t = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("arch", _TRAIN_ARCHS)
def test_microbatched_step_matches_structure(arch):
    """Grad accumulation path traces and yields finite loss (mb=2)."""
    cfg = get_config(arch, smoke=True)
    opt = AdamWCfg()
    state = init_train_state(KEY, cfg, opt)
    step = make_train_step(cfg, opt, constant(1e-3), microbatches=2)
    state, metrics = jax.jit(step)(state, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_init_stage_matches_per_repeat_loop(arch):
    """``init_stage`` draws the repeats under vmap; each block must still be
    bit-identical to drawing repeat r, block i from
    ``fold_in(fold_in(key, r), i)`` one at a time."""
    from repro.models.transformer import init_block, init_stage

    cfg = get_config(arch, smoke=True)
    for stage in cfg.stages:
        got = init_stage(KEY, cfg, stage)
        for r in range(stage.repeats):
            kr = jax.random.fold_in(KEY, r)
            for i, blk in enumerate(stage.pattern):
                want = init_block(jax.random.fold_in(kr, i), cfg, blk)
                have = (got[i] if stage.repeats == 1 else
                        jax.tree.map(lambda x, r=r: x[r], got[i]))
                assert jax.tree.structure(have) == jax.tree.structure(want)
                for h, w in zip(jax.tree.leaves(have), jax.tree.leaves(want)):
                    assert h.dtype == w.dtype
                    np.testing.assert_array_equal(np.asarray(h), np.asarray(w))


def test_param_counts_match_analytic():
    """Analytic param_count (roofline MODEL_FLOPS source) matches real trees
    on smoke configs."""
    from repro.configs import param_count

    for arch in ARCH_NAMES:
        cfg = get_config(arch, smoke=True)
        params = M.init_params(KEY, cfg)
        real = sum(x.size for x in jax.tree.leaves(params))
        pred = param_count(cfg)
        assert abs(real - pred) / real < 0.25, (arch, real, pred)
