"""What the benchmark finds in the program, on the CPU at smoke size: the
engine's host spans in a profiler trace, its requests' stamps and the
readers of them, and the names of the engine's compiled programs against
the cell file's ``trace.step_module``."""
from __future__ import annotations

import json
import re
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import devtrace
import harness
import smallcell
import spec
from repro.analysis import contracts
from repro.configs import get_config
from repro.models import model as M
from repro.serve import spans
from repro.serve.engine import ServeEngine

HERE = Path(__file__).resolve().parent
CELL = json.loads((HERE / "cells" / "qwen2-1.5b.chat.json").read_text())
RECORDED = sorted((HERE / "testdata").glob("trace_*.json"))
STAMP_READERS = ("queue_wait_ms.chat", "prefill_ms.chat")
# what the recorded trace reduced to before the program had spans
RECORDED_BEFORE = {
    "trace_qwen2-1.5b.chat": {
        "steps": 2, "busy_s": 0.385911616, "step_s": 0.38561897900000003,
        "kernel_s": 0.304329077, "collective_s": 0.0,
        "device_ops": [["_ragged_paged_flash.9", 0.304329077],
                       ["constant_dynamic-slice_fusion.20", 0.008999362]],
        "idle_gaps": [["bench.tick", 0.010933263],
                      ["bench.tick", 0.0013281810000000001]]}}
# the phases of a tick that samples, in the order they run
PHASES = [spans.ADMIT, spans.PACK, spans.DISPATCH, spans.WAIT, spans.FETCH,
          spans.SAMPLE]


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-1.5b", smoke=True).replace(dtype="float32")
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _serve(cfg, params):
    """Three 5-token prompts through two slots: each prefill fits one pack
    with its first decode token, so every tick samples, and the third
    request waits in the queue for a slot."""
    eng = ServeEngine(params, cfg, batch_size=2, cache_len=64, page_size=8,
                      prefill_chunk=8, token_budget=16)
    rng = np.random.RandomState(0)
    hs = [eng.submit(rng.randint(0, cfg.vocab_size, 5), max_tokens=3)
          for _ in range(3)]
    ticks = 0
    while not eng.idle:
        eng.tick()
        ticks += 1
    return eng, hs, ticks


def _program_spans(trace_dir):
    """The engine's ``serve.*`` host spans in a profiler trace, in the
    form of ``devtrace.load``'s events."""
    from jax.profiler import ProfileData

    pb = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    return [{"name": e.name, "start_ns": e.start_ns, "dur_ns": e.duration_ns}
            for plane in ProfileData.from_file(str(pb)).planes
            if not devtrace.DEVICE.match(plane.name)
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


def test_spans_nest_in_each_tick_in_order(qwen, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _, _, ticks = _serve(*qwen)
    events = _program_spans(tmp_path)
    tick_evs = [e for e in events if e["name"] == spans.TICK]
    assert len(tick_evs) == ticks

    def inside(e, t):
        return (t["start_ns"] <= e["start_ns"] and e["start_ns"] + e["dur_ns"]
                <= t["start_ns"] + t["dur_ns"])

    others = [e for e in events if e["name"] != spans.TICK]
    assert all(any(inside(e, t) for t in tick_evs) for e in others)
    for t in tick_evs:
        held = sorted((e for e in others if inside(e, t)
                       and e["name"] != spans.MOVERS),
                      key=lambda e: e["start_ns"])
        assert [e["name"] for e in held] == PHASES
    # the movers of an admission round run inside it
    admits = [e for e in others if e["name"] == spans.ADMIT]
    for e in others:
        if e["name"] == spans.MOVERS:
            assert any(inside(e, a) for a in admits)


def test_no_profiler_session_serves_the_same(qwen, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        traced, th, _ = _serve(*qwen)
    plain, ph, _ = _serve(*qwen)
    assert ([h.request.out_tokens for h in ph]
            == [h.request.out_tokens for h in th])
    assert plain.stats == traced.stats
    assert plain.stats["traces"] == 1


def test_request_stamps_are_ordered(qwen):
    _, hs, _ = _serve(*qwen)
    for h in hs:
        r = h.request
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
    # the third request waited for a slot
    assert hs[2].request.admitted_at > hs[0].request.first_token_at


def _module_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def test_step_module_names_the_serve_step_alone():
    """Each engine program, lowered at the analyzer's smoke dimensions
    through the engine's own jitted attributes: only the serve step's
    module is found by the cell file's ``trace.step_module``."""
    cfg, pshapes, sshapes, dims = contracts._smoke_setup()
    eng = ServeEngine(None, cfg, batch_size=dims["B"], cache_len=64,
                      page_size=8, prefill_chunk=dims["chunk"],
                      token_budget=dims["T"], spec_k=dims["spec_k"],
                      kv_dtype="int8")
    step_re = re.compile(CELL["trace"]["step_module"])
    names = {}
    for attr, _, args, *_ in contracts._build_programs(cfg, pshapes,
                                                        sshapes, dims):
        names[attr] = _module_name(getattr(eng, attr).lower(*args))
    assert len(set(names.values())) == len(names), names
    assert [a for a, n in names.items() if step_re.search(n)] == [
        "_ragged_step"], names
    assert eng.stats["traces"] == 1  # the trace counter still counts it


def _read(name, window, tracked):
    run = types.SimpleNamespace(window=window, notes=[],
                                drv=types.SimpleNamespace(tracked=tracked))
    return spec.reader(name)(run)


def _tracked(idx, **stamps):
    return types.SimpleNamespace(req=types.SimpleNamespace(idx=idx),
                                 request=types.SimpleNamespace(**stamps))


def test_stamp_readers_take_the_window_and_the_schedule():
    """Medians (nearest rank) over the schedule's requests (``idx >= 0``;
    the warm-up's are negative) admitted, or given their first token, in
    the window."""
    tracked = [_tracked(0, submitted_at=9.0, admitted_at=9.5,
                        first_token_at=10.0),   # both before the window
               _tracked(1, submitted_at=10.0, admitted_at=10.25,
                        first_token_at=11.25),
               _tracked(2, submitted_at=11.0, admitted_at=11.5,
                        first_token_at=13.5),
               _tracked(3, submitted_at=12.0, admitted_at=12.75,
                        first_token_at=15.0),   # at the window's close
               _tracked(-1, submitted_at=10.0, admitted_at=14.0,
                        first_token_at=14.5),   # warm-up
               _tracked(4, submitted_at=14.0, admitted_at=20.0,
                        first_token_at=21.0)]   # admitted after the close
    window = (10.0, 15.0)
    assert _read("queue_wait_ms.chat", window, tracked) == pytest.approx(500)
    assert _read("prefill_ms.chat", window, tracked) == pytest.approx(2000)


@pytest.mark.parametrize("name", STAMP_READERS)
def test_stamp_readers_give_nothing_without_stamps(name):
    """A program whose requests carry no stamps, as before the engine had
    them, and a window in which no request was admitted."""
    unstamped = types.SimpleNamespace(req=types.SimpleNamespace(idx=0),
                                      request=types.SimpleNamespace(
                                          out_tokens=[1]))
    assert _read(name, (0.0, 1.0), [unstamped]) is None
    assert _read(name, (0.0, 1.0), [_tracked(0, submitted_at=2.0,
                                             admitted_at=3.0,
                                             first_token_at=4.0)]) is None


def test_stamp_readers_on_small_twin(tmp_path, monkeypatch):
    """A traced run of the chat cell's small twin on the CPU.  The CPU
    has no device plane: one op over the traced window stands in for the
    device's, which neither reader reads."""
    load = devtrace.load

    def with_stand_in(trace_dir):
        events = load(trace_dir)
        w = next(e for e in events if e["name"] == "bench.traced")
        return events + [{"plane": "/device:TPU:0", "line": "XLA Ops",
                          "name": "%stand_in", "start_ns": w["start_ns"],
                          "dur_ns": w["dur_ns"]}]

    monkeypatch.setattr(devtrace, "load", with_stand_in)
    c = spec.load(smallcell.make(tmp_path, "qwen2-1.5b.chat"), tmp_path)
    c.settings["trace"].update(start_s=1.0, seconds=3.0)
    c.per_layer = [m for m in c.per_layer if m["name"] in STAMP_READERS]
    out = harness.run_cell(c, 2 ** 31 + 4321, 6.0, True, jax.devices()[:1],
                           time.perf_counter())
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert sorted(got) == sorted(STAMP_READERS)
    assert all(v > 0 for v in got.values()), got


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_chip_trace_reduces_as_before(path):
    """The chip trace recorded before the program had spans and named
    programs reduces to exactly what it did then."""
    rec = json.loads(path.read_text())
    assert rec["cell"] == "qwen2-1.5b.chat"
    r = devtrace.Reduced(rec["events"], step_module=CELL["trace"]["step_module"],
                         kernel=CELL["trace"]["kernel"])
    want = RECORDED_BEFORE[path.stem]
    for prop in ("steps", "busy_s", "step_s", "kernel_s", "collective_s"):
        assert getattr(r, prop) == want[prop], prop
    assert r.device_ops(2) == want["device_ops"]
    assert r.idle_gaps(2) == want["idle_gaps"]
