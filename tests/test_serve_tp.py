"""Tensor-parallel serving invariance suite.

The engine's ``mesh=`` contract (serve/engine.py): the paged KV pools shard
over the KV-head axis while ALL host bookkeeping stays global, so the same
seeded traffic must produce TOKEN-IDENTICAL output at every device count,
with zero page leaks and exactly one traced serve program per count.  Each
device count runs in a subprocess with forked fake devices (see
conftest.run_multidevice); the parent compares canonical transcripts.

qwen1.5-4b smoke is the config under test: its global-attention layers have
num_kv_heads == 4, so the pools genuinely split 4 ways (qwen2-1.5b smoke has
kvH == 2 and could not).
"""
import pytest

# Seeded mixed traffic: staggered submits, prefix-sharing family prompts,
# mid-flight cancels, int8 pools — everything the engine's bookkeeping
# touches; prints a canonical transcript plus the in-process invariants.
_DRIVER = """
import jax, numpy as np
from repro.configs import get_config
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.pool import kv_page_bytes

N_DEV = {n}
cfg = get_config("qwen1.5-4b", smoke=True)
params = M.init_params(jax.random.PRNGKey(0), cfg)
kw = dict(batch_size=2, cache_len=64, page_size=8, prefill_chunk=8,
          token_budget=16, kv_dtype="int8", flash_decode={flash})
if N_DEV > 1:
    from repro.launch.mesh import make_mesh
    kw["mesh"] = make_mesh((N_DEV,), ("model",))
eng = ServeEngine(params, cfg, **kw)

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
"""


def _transcript(multidevice, n_devices: int, flash: bool) -> str:
    out = multidevice(_DRIVER.format(n=n_devices, flash=flash),
                      n_devices=n_devices, timeout=900)
    lines = [l for l in out.splitlines() if l.startswith("TRANSCRIPT")]
    assert lines, out
    return lines[-1]


@pytest.mark.slow
def test_token_identity_across_device_counts(multidevice):
    """Same seeds, same traffic, device counts {1, 2, 4}: token-identical
    transcripts, zero leaks, one trace per count (asserted in-process)."""
    t1 = _transcript(multidevice, 1, flash=False)
    t2 = _transcript(multidevice, 2, flash=False)
    t4 = _transcript(multidevice, 4, flash=False)
    assert t1 == t2, f"{t1}\nvs\n{t2}"
    assert t1 == t4, f"{t1}\nvs\n{t4}"


@pytest.mark.slow
def test_token_identity_flash_kernel_path(multidevice):
    """The Pallas flash path (shard_map'd over KV heads in
    serve.decode_attention) preserves the same identity contract."""
    t1 = _transcript(multidevice, 1, flash=True)
    t4 = _transcript(multidevice, 4, flash=True)
    assert t1 == t4, f"{t1}\nvs\n{t4}"


def test_sharded_scenario_refuses_a_tpu_parent(monkeypatch):
    """The forked-device scenario measures CPU children; from a parent on
    a TPU backend it must refuse (that parent holds the chip) and point to
    the in-process four-chip path instead of reporting CPU numbers."""
    import importlib.util
    from pathlib import Path

    import jax

    path = Path(__file__).resolve().parents[1] / "benchmarks" / "serve_sweep.py"
    spec = importlib.util.spec_from_file_location("serve_sweep", path)
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="chip_smoke.py --four-chips"):
        sweep.sharded_serve_scenario()
