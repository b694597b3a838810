"""The reduction from a profiler trace to device times, on a made-up trace
whose answers are known and on a small trace recorded on the chip."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import devtrace

HERE = Path(__file__).resolve().parent
RECORDED = sorted((HERE / "testdata").glob("trace_*.json"))
MS = 1_000_000


def ev(plane, line, name, start_ms, dur_ms):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": int(start_ms * MS), "dur_ns": int(dur_ms * MS)}


def made_up():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    out = [ev("/host:CPU", "python", "bench.traced", 0, 100),
           ev("/host:CPU", "python", "bench.tick", 0, 45),
           ev("/host:CPU", "python", "bench.stamp", 45, 5),
           ev("/host:CPU", "python", "bench.tick", 50, 50)]
    for d in (d0, d1):
        out += [ev(d, "XLA Modules", "jit_wrapper(1)", 10, 30),
                ev(d, "XLA Modules", "jit_wrapper(1)", 60, 30),
                ev(d, "XLA Modules", "jit_other(2)", 95, 2),
                # a scan's loop holding its ops, then a collective, in line
                ev(d, "XLA Ops", "%while.1 = (s32[]) while(...)", 10, 30),
                ev(d, "XLA Ops", "%fusion.1 = bf16[8] fusion(...)", 10, 10),
                ev(d, "XLA Ops", "%_ragged_paged_flash.9 = custom-call()", 20, 15),
                ev(d, "XLA Ops", "%all-gather.3 = all-gather()", 35, 5),
                ev(d, "XLA Ops", "%fusion.1 = bf16[8] fusion(...)", 60, 10),
                ev(d, "XLA Ops", "%_ragged_paged_flash.9 = custom-call()", 70, 15),
                # an asynchronous collective, half beside the kernel
                ev(d, "Async XLA Ops", "%all-gather-start.2 = ...", 80, 10),
                ev(d, "XLA Ops", "%fusion.2 = f32[] fusion(...)", 95, 2)]
    out.append(ev(d0, "XLA Ops", "fusion.9", 120, 5))  # after the window
    return out


def test_made_up_trace():
    r = devtrace.Reduced(made_up(), step_module="wrapper",
                         kernel="_ragged_paged_flash")
    assert r.window_s == pytest.approx(0.1)
    assert r.steps == 2
    assert r.step_s == pytest.approx(0.060)
    # busy: [10, 40] + [60, 85] + [95, 97]; the asynchronous
    # collective is not an op of the device's cores
    assert r.busy_s == pytest.approx(0.057)
    assert r.kernel_s == pytest.approx(0.030)
    assert r.collective_s == pytest.approx(0.015)
    assert r.collective_exposed_s == pytest.approx(0.010)
    ops = dict(r.device_ops())
    assert ops["_ragged_paged_flash.9"] == pytest.approx(0.030)
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["while.1"] == pytest.approx(0.0)
    gaps = r.idle_gaps()
    # the longest gaps: [40, 60] across stamp and tick (the innermost span
    # at its middle names it), then [0, 10] in bench.tick
    assert gaps[0] == ["bench.stamp", pytest.approx(0.020)]
    assert gaps[1] == ["bench.tick", pytest.approx(0.010)]


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        devtrace.Reduced([e for e in made_up() if e["name"] != "bench.traced"],
                         step_module="wrapper", kernel="k")
    with pytest.raises(ValueError):
        devtrace.Reduced([e for e in made_up() if "device" not in e["plane"]],
                         step_module="wrapper", kernel="k")


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    rec = json.loads(path.read_text())
    cell = json.loads((HERE / "cells" / f"{rec['cell']}.json").read_text())
    r = devtrace.Reduced(rec["events"], **{k: cell["trace"][k] for k in
                                           ("step_module", "kernel")})
    assert r.steps >= 1
    assert 0 < r.kernel_s < r.step_s <= r.window_s * len(r.devices)
    assert 0 < r.busy_s <= r.window_s
    assert r.device_ops() and r.idle_gaps()
