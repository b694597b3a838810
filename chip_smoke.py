"""Chip smoke test: the serving engine's main path on a TPU at published width.

One chip (the default): qwen2-1.5b at its published width (28 layers,
d_model 1536, GQA 12/2 heads, head_dim 128, vocab 151,936), with weights
random-initialised from ``--seed``, serves 8 requests through
``ServeEngine``'s ragged token-budget step with the Pallas
``ragged_paged_flash`` kernel: prompts of 64 to 1,500 tokens, two of them
sharing a 512-token prefix (the second arrives after the first finished,
so it hits the prefix cache), 32 new tokens each.  It runs once with the
default bf16 KV pool and once with an int8 pool, and each time the same
requests go through the engine's jnp attention path on the chip as the
reference.  Checks:

- the compiled serve step contains the Pallas kernel (``tpu_custom_call``);
- the serve step traced exactly once (``stats["traces"] == 1``);
- every request's first generated token agrees between kernel and jnp,
  unless the reference's top two logits lie within the tolerance;
- the first-token logits agree within ``LOGIT_TOL``;
- no page leaks: after ``drop_prefix_cache()`` the free pages are back at
  their start value.

Four chips (``--four-chips``, and nothing else then): qwen1.5-4b at its
published width (MHA 20 heads, bf16 params) served KV-head tensor-parallel
over a 4-device mesh, against the same engine with no mesh on one of the
four chips, in this one process: B=4, cache_len 2048, prompts of 64 to
1,500 tokens, one pair sharing a 512-token prefix.  Checks token-identical
transcripts, a prefix-cache hit, four KV shards, and that every pool leaf
really is split over four devices.

Exits non-zero when JAX finds no TPU or any check fails, and prints no
result then.  On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Wall times printed on the way are smoke timings, not measurements.

  python chip_smoke.py [--seed S] [--four-chips]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

# kernel-vs-jnp tolerance on first-token logits, as a fraction of the
# reference row's largest magnitude.  Logits come out of the bf16 LM head,
# whose rounding step near the row's top is 2^-8..2^-7 of it, so the bound
# is a few such steps; a wrong page, head or scale moves the row by the
# order of its standard deviation over the vocabulary (~sqrt(d_model) with
# random weights), several times the bound.  The same absolute bound marks
# a reference top-2 pair a near-tie.
LOGIT_TOL = 0.03

ONE_CHIP = dict(arch="qwen2-1.5b", batch_size=8, cache_len=2048,
                page_size=16, token_budget=128, prefill_chunk=32,
                max_tokens=32, prefix_len=512,
                # (wave, prompt length); a 512-token prefix is shared by the
                # 640-token wave-1 prompt and the 812-token wave-2 prompt
                prompts=((1, 64), (1, 1500), (1, 200), (1, 640), (1, 400),
                         (1, 1000), (1, 800), (2, 812)),
                shared=(3, 7))
# qwen1.5-4b's KV is ~400 KB per token in bf16 (a 3.4 GB pool at B=4), and
# the one-chip reference also holds the 7.9 GB of params, so B is 4
FOUR_CHIPS = dict(arch="qwen1.5-4b", batch_size=4, cache_len=2048,
                  page_size=16, token_budget=128, prefill_chunk=32,
                  max_tokens=32, prefix_len=512,
                  prompts=((1, 64), (1, 1500), (1, 200), (1, 640), (1, 400),
                           (2, 812)),
                  shared=(3, 5))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


def _log(msg: str) -> None:
    print(msg, flush=True)


def _prompts(spec, vocab: int, seed: int):
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, spec["prefix_len"])
    out = []
    for i, (wave, n) in enumerate(spec["prompts"]):
        p = rng.randint(0, vocab, n).astype(np.int32)
        if i in spec["shared"]:
            p[:len(prefix)] = prefix
        out.append((wave, p))
    return out


def _init_params(cfg, seed: int, sharding=None):
    import jax

    from repro.models import model as M

    init = jax.jit(lambda k: M.init_params(k, cfg), out_shardings=sharding)
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def _engine(params, cfg, spec, **kw):
    """A ServeEngine that also keeps each request's first sampled logits
    row (the comparison needs logits, which the engine only samples)."""
    from repro.serve.engine import ServeEngine

    class Recording(ServeEngine):
        def _sample(self, req, logits_row, ordinal):
            if ordinal == 0:
                self.first_rows[int(req.uid)] = (
                    self.stats["ticks"], np.array(logits_row, np.float32))
            return super()._sample(req, logits_row, ordinal)

    eng = Recording(params, cfg, batch_size=spec["batch_size"],
                    cache_len=spec["cache_len"], page_size=spec["page_size"],
                    token_budget=spec["token_budget"],
                    prefill_chunk=spec["prefill_chunk"], **kw)
    eng.first_rows = {}
    return eng


def _serve(eng, prompts, max_tokens: int):
    """Submit wave by wave, draining between waves; returns transcripts in
    submission order and the smoke wall time."""
    out = {}
    t0 = time.perf_counter()
    for wave in sorted({w for w, _ in prompts}):
        uids = [eng.submit(p, max_tokens=max_tokens)
                for w, p in prompts if w == wave]
        res = eng.run()
        out.update({int(u): list(res[u]) for u in uids})
    return [out[u] for u in sorted(out)], time.perf_counter() - t0


def _step_hlo(eng) -> str:
    """Compiled text of the engine's serve step at its live shapes."""
    import jax

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=getattr(x, "sharding", None))

    T, B = eng.budget, eng.B
    vec = jax.ShapeDtypeStruct((T,), np.int32)
    args = (jax.tree.map(shape, eng.params), jax.tree.map(shape, eng._state),
            vec, vec, vec, vec, jax.ShapeDtypeStruct((T,), np.bool_),
            jax.ShapeDtypeStruct((B,), np.int32))
    with eng._ctx():
        return eng._ragged_step.lower(*args).compile().as_text()


def _run_path(params, cfg, spec, prompts, *, kv_dtype, flash):
    import jax

    eng = _engine(params, cfg, spec, kv_dtype=kv_dtype, flash_decode=flash)
    free0 = eng.pool.free_pages
    outs, wall = _serve(eng, prompts, spec["max_tokens"])
    st = eng.stats
    name = f"{kv_dtype}/{'kernel' if flash else 'jnp'}"
    _log(f"[{name}] smoke timing (compile included): {wall:.1f} s for "
         f"{st['ticks']} ticks; traces={st['traces']} "
         f"prefix_hits={st['prefix_hits']} "
         f"prefix_tokens_reused={st['prefix_tokens_reused']} "
         f"n_pages={eng.n_pages}")
    _check(st["traces"] == 1, f"{name}: serve step traced {st['traces']}x")
    _check(all(len(o) == spec["max_tokens"] for o in outs),
           f"{name}: a request stopped short")
    if spec["shared"]:
        _check(st["prefix_hits"] >= 1, f"{name}: no prefix-cache hit")
    has_kernel = "tpu_custom_call" in _step_hlo(eng)
    _log(f"[{name}] compiled serve step contains tpu_custom_call: "
         f"{has_kernel}")
    _check(has_kernel == flash,
           f"{name}: Pallas kernel {'missing from' if flash else 'in'} "
           "the compiled step")
    eng.drop_prefix_cache()
    _log(f"[{name}] free pages after drop_prefix_cache: "
         f"{eng.pool.free_pages} (start {free0})")
    _check(eng.pool.free_pages == free0 and eng.pool.pages_in_use == 0,
           f"{name}: pages leaked")
    first = eng.first_rows
    del eng
    gc.collect()
    mem = jax.devices()[0].memory_stats() or {}
    _log(f"[{name}] device 0 peak_bytes_in_use: "
         f"{mem.get('peak_bytes_in_use', 'not reported')}")
    return outs, first


def _compare_first_tokens(kv_dtype, ker, ref) -> None:
    _check(sorted(ker) == sorted(ref), f"{kv_dtype}: sampled uids differ")
    first_tick = min(t for t, _ in ref.values())
    worst, worst_first, agree, agree_first, ties = 0.0, 0.0, 0, 0, 0
    n_first = 0
    for uid in sorted(ref):
        tick, r = ref[uid]
        k = ker[uid][1]
        tol = LOGIT_TOL * float(np.max(np.abs(r)))
        d = float(np.max(np.abs(k - r)))
        top2 = np.sort(r)[-2:]
        same = int(np.argmax(k)) == int(np.argmax(r))
        near_tie = float(top2[1] - top2[0]) <= tol
        _log(f"[{kv_dtype}] uid {uid}: first token kernel "
             f"{int(np.argmax(k))} jnp {int(np.argmax(r))}; max|dlogit| "
             f"{d:.4f} (tol {tol:.4f}); reference top-2 gap "
             f"{float(top2[1] - top2[0]):.4f}; reference max|logit| "
             f"{float(np.max(np.abs(r))):.4f} std {float(np.std(r)):.4f}")
        _check(d <= tol, f"{kv_dtype} uid {uid}: max|dlogit| {d} > {tol}")
        _check(same or near_tie,
               f"{kv_dtype} uid {uid}: first tokens differ, not a near-tie")
        worst, agree, ties = max(worst, d), agree + same, ties + (not same)
        if tick == first_tick:
            n_first += 1
            worst_first = max(worst_first, d)
            agree_first += same
    _log(f"[{kv_dtype}] first sampled pack (tick {first_tick}, {n_first} "
         f"rows): max|dlogit| {worst_first:.4f}, top-1 agreement "
         f"{agree_first}/{n_first}")
    _log(f"[{kv_dtype}] all first tokens: top-1 agreement {agree}/{len(ref)}"
         f" (near-ties excused: {ties}); max|dlogit| {worst:.4f}; "
         f"tolerance {LOGIT_TOL} x max|logit| of the reference row")


def one_chip(seed: int) -> None:
    import jax

    from repro.configs import get_config

    spec = ONE_CHIP
    cfg = get_config(spec["arch"])
    t0 = time.perf_counter()
    params = _init_params(cfg, seed)
    n_param = sum(x.size for x in jax.tree.leaves(params))
    _log(f"{cfg.name}: {cfg.n_layers} layers, {n_param} params "
         f"({cfg.param_dtype}), init smoke timing "
         f"{time.perf_counter() - t0:.1f} s")
    prompts = _prompts(spec, cfg.vocab_size, seed)
    for kv_dtype in ("bfloat16", "int8"):
        ker_out, ker = _run_path(params, cfg, spec, prompts,
                                 kv_dtype=kv_dtype, flash=True)
        ref_out, ref = _run_path(params, cfg, spec, prompts,
                                 kv_dtype=kv_dtype, flash=False)
        _compare_first_tokens(kv_dtype, ker, ref)
        same = sum(a == b for a, b in zip(ker_out, ref_out))
        _log(f"[{kv_dtype}] whole transcripts identical kernel vs jnp: "
             f"{same}/{len(ref_out)} (divergence after a first-token "
             "near-tie is allowed)")


def four_chips(seed: int) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh

    _check(len(jax.devices()) >= 4,
           f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    spec = FOUR_CHIPS
    cfg = get_config(spec["arch"])
    prompts = _prompts(spec, cfg.vocab_size, seed)

    # reference: no mesh, one chip; freed before the mesh run needs room
    params = _init_params(cfg, seed, SingleDeviceSharding(jax.devices()[0]))
    eng = _engine(params, cfg, spec, flash_decode=True)
    ref_out, wall = _serve(eng, prompts, spec["max_tokens"])
    st = eng.stats
    _log(f"[1 chip] smoke timing (compile included): {wall:.1f} s, "
         f"traces={st['traces']} prefix_hits={st['prefix_hits']} "
         f"prefix_tokens_reused={st['prefix_tokens_reused']} "
         f"kv_pool_bytes={st['kv_pool_bytes']}")
    _check(st["traces"] == 1, f"1 chip: serve step traced {st['traces']}x")
    _check(st["prefix_hits"] >= 1, "1 chip: no prefix-cache hit")
    del eng, params
    gc.collect()
    mem = jax.devices()[0].memory_stats() or {}
    _log(f"[1 chip] device 0 peak_bytes_in_use: "
         f"{mem.get('peak_bytes_in_use', 'not reported')}")

    mesh = make_mesh((4,), ("model",))
    params = _init_params(cfg, seed, NamedSharding(mesh, P()))
    eng = _engine(params, cfg, spec, flash_decode=True, mesh=mesh)
    free0 = eng.pool.free_pages
    tp_out, wall = _serve(eng, prompts, spec["max_tokens"])
    st = eng.stats
    _log(f"[4 chips] smoke timing (compile included): {wall:.1f} s, "
         f"traces={st['traces']} prefix_hits={st['prefix_hits']} "
         f"prefix_tokens_reused={st['prefix_tokens_reused']} "
         f"kv_shards={st['kv_shards']} "
         f"kv_pool_bytes={st['kv_pool_bytes']} "
         f"kv_pool_bytes_per_device={st['kv_pool_bytes_per_device']}")
    _check(st["traces"] == 1, f"4 chips: serve step traced {st['traces']}x")
    _check(st["prefix_hits"] >= 1, "4 chips: no prefix-cache hit")
    _check(st["kv_shards"] == 4, f"kv_shards {st['kv_shards']} != 4")
    kvH = cfg.stages[0].pattern[0].attn.num_kv_heads
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng._state):
        if jax.tree_util.keystr(path[-1:]) in ("['kp']", "['vp']"):
            shards = leaf.addressable_shards
            devs = {s.device for s in shards}
            heads = {s.data.shape[-3] for s in shards}
            _check(len(devs) == 4 and heads == {kvH // 4},
                   f"pool {jax.tree_util.keystr(path)} on {len(devs)} "
                   f"devices with per-shard heads {heads}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:4]]
    _log(f"[4 chips] every KV pool split over 4 devices, {kvH // 4} heads "
         f"each; bytes_in_use per device: {in_use}")
    has_kernel = "tpu_custom_call" in _step_hlo(eng)
    _log(f"[4 chips] compiled serve step contains tpu_custom_call: "
         f"{has_kernel}")
    _check(has_kernel, "4 chips: Pallas kernel missing from the step")
    eng.drop_prefix_cache()
    _check(eng.pool.free_pages == free0 and eng.pool.pages_in_use == 0,
           "4 chips: pages leaked")
    same = sum(a == b for a, b in zip(tp_out, ref_out))
    _log(f"transcripts token-identical 4 chips vs 1 chip: "
         f"{same}/{len(ref_out)}")
    _check(same == len(ref_out), "4-chip transcripts differ from 1 chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip KV-head tensor-parallel path "
                         "and its one-chip comparison")
    args = ap.parse_args(argv)

    # before libtpu loads: it otherwise logs to a fixed directory outside
    # the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this script runs only on the chip",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"device: {dev.device_kind} x{len(jax.devices())}; jax "
         f"{jax.__version__}; compile cache {enable_compile_cache()}")
    (four_chips if args.four_chips else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
