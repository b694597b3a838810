"""Readings that set a cell's ``logit_gap`` limit: the program's and the
control's, over many seeds in one process (set-up is long).

    python bench/control.py --workload <name> --seconds <s> --seeds 1 2 3 ...

For each seed: one run of the cell as ``bench/run.py`` makes it (the
window at the cell's own load, here ``--seconds`` long), the program's
widest gap as the run reports it, and over the same sample of served
requests the control's: the float32 reference put in the program's place
with every product in float8 (e4m3), one step below the bfloat16 the
configuration serves in, reading at each position the gap of the token
that float8 ranks first.  Both gaps are also read in a plain decode's
alignment, without the engine's hand-off duplicate (``check.py``).  One
JSON line per seed goes to standard output.
The benchmark's own runs never run the control.  Chip only, like run.py.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import spec

    cell = spec.load(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the chip", file=sys.stderr)
        return 2
    import check
    import harness
    import program

    program.enable_compile_cache()
    cache_len = cell.settings["engine"]["cache_len"]

    def control(run, ref, weights, picked):
        return {k: check.widest_gap(ref, weights, cell.config, picked,
                                    cache_len, control=c, handoff=h)
                for k, c, h in (("control_gap", True, True),
                                ("program_gap_plain", False, False),
                                ("control_gap_plain", True, False))}

    t = T_START
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               devices[:cell.chips], t, after=control)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program_gap": out["checks"]["logit_gap"]["value"],
                          **out["also"],
                          "metrics": out["metrics"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
