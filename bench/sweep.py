"""Rate sweep of an open-loop cell, to find its knee once: the highest
session rate at which time to first token does not grow over the window.

    python bench/sweep.py --workload <name> --seconds <s> --seed <n> --rates 0.4 0.6 ...

Each rate is one run of the cell (``bench/run.py``'s, in this process)
with the traffic file's ``arrivals.rate_per_s`` replaced; one JSON line per
rate gives the requests' rate, the median time to first token of the
window's first and second halves, the requests still waiting for a first
token at the close, and the end-to-end metrics.  Chip only, like run.py.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import spec

    base = spec.load(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < base.chips:
        print("sweep: needs the chip", file=sys.stderr)
        return 2
    import harness
    import program
    import tails

    program.enable_compile_cache()
    t = T_START
    for rate in args.rates:
        tr = copy.deepcopy(base.traffic)
        tr["arrivals"]["rate_per_s"] = rate
        cell = dataclasses.replace(base, traffic=tr)
        out = harness.run_cell(cell, args.seed, args.seconds, False,
                               devices[:base.chips], t,
                               after=lambda run, *_: run)
        run = out.pop("also")
        lo, hi = run.window
        mid = (lo + hi) / 2
        halves = []
        for a, b in ((lo, mid), (mid, hi)):
            part = dataclasses.replace(run, window=(a, b))
            halves.append(tails.percentile(tails.ttft_s(part), 50))
        waiting = sum(1 for x in run.drv.tracked if x.req.due is not None
                      and lo <= x.due < hi and not (x.times and x.times[0] <= hi))
        print(json.dumps({
            "rate_per_s": rate, "requests_per_s": out["attempted"] / (hi - lo),
            "ttft_p50_first_half_s": halves[0],
            "ttft_p50_second_half_s": halves[1],
            "waiting_at_close": waiting, "attempted": out["attempted"],
            "correct": out["correct"], "metrics": out["metrics"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
