"""The benchmark's one command: one run of one cell on the chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell named in ``BENCHMARK.json``, warms up, measures for
``--seconds``, compares what was served with the plain reference, and
prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared beside its limit, which also end standard error.

Exits 2 with no result where JAX finds no TPU, or fewer chips than the
cell asks for.  It never falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="also write the trace's events as JSON here")
    args = ap.parse_args(argv)

    # before libtpu loads: it otherwise logs to a fixed directory outside
    # the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import spec

    cell = spec.load(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    import harness
    import program

    print(f"compile cache: {program.enable_compile_cache()}", file=sys.stderr)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices[:cell.chips], T_START,
                           keep_trace=args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
