"""Tensor-parallel serving invariance suite.

The engine's ``mesh=`` contract (serve/engine.py): the paged KV pools shard
over the KV-head axis while ALL host bookkeeping stays global, so the same
seeded traffic must produce TOKEN-IDENTICAL output at every device count,
with zero page leaks and exactly one traced serve program per count.  Each
device count runs in a subprocess with forked fake devices (see
conftest.run_multidevice); the parent compares canonical transcripts and,
on the flash path, first-token logits and KV pool digests bit for bit.

qwen1.5-4b smoke is the config under test: its global-attention layers have
num_kv_heads == 4, so the pools genuinely split 4 ways (qwen2-1.5b smoke has
kvH == 2 and could not).
"""
import json

import numpy as np
import pytest

# Seeded mixed traffic: staggered submits, prefix-sharing family prompts,
# mid-flight cancels — everything the engine's bookkeeping touches; prints a
# canonical transcript, each request's first-token logits and a digest of
# every KV pool leaf, plus the in-process invariants.
_SCENARIO = """
import hashlib, json
import jax, numpy as np
from repro.configs import get_config
from repro.models import model as M
from repro.serve.engine import ServeEngine

N_DEV = {n}
cfg = get_config("qwen1.5-4b", smoke=True)
params = M.init_params(jax.random.PRNGKey(0), cfg)
# QKV biases as a trained model has them: the initializer's are zero, and
# a zero bias hides how the bias add before the rotary embedding rounds
params = jax.tree_util.tree_map_with_path(
    lambda p, x: jax.random.normal(jax.random.PRNGKey(1), x.shape, x.dtype)
    if jax.tree_util.keystr(p[-1:]) in ("['bq']", "['bk']", "['bv']") else x,
    params)
kw = dict(batch_size=2, cache_len=64, page_size=8, prefill_chunk=8,
          token_budget=16, kv_dtype="{kv_dtype}", flash_decode={flash})
if N_DEV > 1:
    from repro.launch.mesh import make_mesh
    kw["mesh"] = make_mesh((N_DEV,), ("model",))
first = {{}}


class Recording(ServeEngine):
    def _sample(self, req, logits_row, ordinal):
        if ordinal == 0:
            first[int(req.uid)] = np.asarray(logits_row, np.float32).tolist()
        return super()._sample(req, logits_row, ordinal)


eng = Recording(params, cfg, **kw)

rng = np.random.default_rng(7)
[family] = [rng.integers(1, cfg.vocab_size, size=16)]
handles = []
for i in range(6):
    if i % 2:
        prompt = np.concatenate([family, rng.integers(1, cfg.vocab_size,
                                                      size=1 + i)])
    else:
        prompt = rng.integers(1, cfg.vocab_size, size=int(rng.integers(3, 24)))
    handles.append(eng.submit(prompt, max_tokens=4 + i % 5))
    eng.tick()
handles[2].cancel()
res = eng.run()

# host bookkeeping must be device-count-agnostic
assert eng.stats["traces"] == 1, eng.stats["traces"]
assert (eng._ref == 0).all()
assert eng.reclaimable_pages == eng.n_pages
assert eng.stats["kv_shards"] == (N_DEV if N_DEV > 1 else 1)
assert eng.stats["n_devices"] == N_DEV
# per-device pool bytes shrink by the shard count (kvH=4 divides exactly)
assert eng.stats["kv_pool_bytes_per_device"] * eng.stats["kv_shards"] \\
    == eng.stats["kv_pool_bytes"]
print("TRANSCRIPT", sorted((int(k), tuple(v)) for k, v in res.items()))
print("FIRST_LOGITS", json.dumps(sorted(first.items())))
print("POOLS", json.dumps({{
    jax.tree_util.keystr(p): hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
    for p, x in jax.tree_util.tree_leaves_with_path(eng._state)
    if jax.tree_util.keystr(p[-1:]) in ("['kp']", "['vp']", "['ks']", "['vs']")}}))
"""


def _run(multidevice, n_devices: int, flash: bool, kv_dtype: str = "int8"):
    """(transcript line, first-token logits by uid, pool digests)."""
    out = multidevice(_SCENARIO.format(n=n_devices, flash=flash,
                                     kv_dtype=kv_dtype),
                      n_devices=n_devices, timeout=900)

    def last(tag):
        lines = [l for l in out.splitlines() if l.startswith(tag + " ")]
        assert lines, out
        return lines[-1][len(tag) + 1:]

    return (last("TRANSCRIPT"), dict(json.loads(last("FIRST_LOGITS"))),
            json.loads(last("POOLS")))


@pytest.mark.slow
def test_token_identity_across_device_counts(multidevice):
    """Same seeds, same traffic, device counts {1, 2, 4}: token-identical
    transcripts, zero leaks, one trace per count (asserted in-process)."""
    t1, t2, t4 = (_run(multidevice, n, False)[0] for n in (1, 2, 4))
    assert t1 == t2, f"{t1}\nvs\n{t2}"
    assert t1 == t4, f"{t1}\nvs\n{t4}"


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", ["int8", "bfloat16"])
def test_token_identity_flash_kernel_path(multidevice, kv_dtype):
    """The Pallas flash path (shard_map'd over KV heads in
    serve.decode_attention) preserves the same identity contract, in the
    int8 pool and in the bf16 pool the benchmark's four-chip cell serves
    from: identical transcripts, first-token logits equal bit for bit, and
    every pool leaf written bit for bit alike (the pools show a step that
    rounds differently on four devices before any sampled token does)."""
    t1, l1, p1 = _run(multidevice, 1, True, kv_dtype)
    t4, l4, p4 = _run(multidevice, 4, True, kv_dtype)
    assert t1 == t4, f"{t1}\nvs\n{t4}"
    assert sorted(l1) == sorted(l4)
    for uid in l1:
        a, b = np.asarray(l1[uid]), np.asarray(l4[uid])
        assert np.array_equal(a, b), (uid, float(np.max(np.abs(a - b))))
    assert p1 == p4, [k for k in p1 if p1[k] != p4.get(k)]
