"""Four-device compile rehearsal of a cell's serve step, with no chip.

    JAX_PLATFORMS=cpu python bench/rehearse_tp4.py [--workload NAME] [--batch B ...]

Compiles the engine's ragged serve step for a described v5e 2x2 (four
chips, KV-head tensor parallel as the cell runs it) at the cell's sizes,
or at each ``--batch`` given, and prints per device the bytes of the
arguments, the temporaries and their sum against the chip's 16 GiB, the
collectives in the compiled text, and whether the Pallas kernel is in it.
Nothing runs: it says whether the step fits and compiles, not how fast.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HBM = 16 * 2 ** 30


def rehearse(cell, batch: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import check
    import program
    from repro.kernels import flash_attention, ops
    from repro.models import model as M
    from repro.serve.engine import ServeEngine
    from repro.serve.serve_step import decode_state_specs

    # the CPU backend would pick the kernels' interpret mode
    flash_attention.default_interpret = ops.default_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:cell.chips]), ("model",))
    hf, eng_cfg = cell.config, dict(cell.settings["engine"], batch_size=batch)
    cfg = program.model_cfg(hf)
    ref = check.reference_module(cell.reference)
    eng = ServeEngine(None, cfg, mesh=mesh, **eng_cfg)
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(lambda k: ref.make_weights(k, hf),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), params)
    state = jax.eval_shape(lambda p: M.init_paged_state(
        p, cfg, batch, eng.cache_len, page_size=eng.page_size,
        n_pages=eng.n_pages, window_extra=eng.chunk, kv_dtype=eng.kv_dtype),
        params)
    with eng._ctx():
        specs = decode_state_specs(state)
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        state, specs, is_leaf=lambda x: isinstance(x, P))
    T = eng.budget
    vec = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=rep)
    args = (params, state, vec, vec, vec, vec,
            jax.ShapeDtypeStruct((T,), jnp.bool_, sharding=rep),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=rep))
    with eng._ctx():
        compiled = eng._ragged_step.lower(*args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    arg, tmp = mem.argument_size_in_bytes, mem.temp_size_in_bytes
    return {"batch": batch, "n_pages": eng.n_pages,
            "argument_bytes": arg, "temp_bytes": tmp, "sum_bytes": arg + tmp,
            "fits_16GiB": arg + tmp < HBM,
            "all_gathers": text.count("all-gather-start")
            or text.count("all-gather("),
            "kernel_in_step": "tpu_custom_call" in text}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="qwen1.5-4b.tp4-decode")
    ap.add_argument("--batch", type=int, nargs="*", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import spec

    cell = spec.load(args.workload)
    for b in args.batch or [cell.settings["engine"]["batch_size"]]:
        print(rehearse(cell, b), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
