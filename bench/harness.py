"""One run of one cell: set up, warm up, measure, check, report.

``run_cell`` does everything after the look for a chip, so a test can
drive a whole run on the CPU at a small size.  The order of a run:

1. weights from the seed, in one jitted call on the device, in the dtype
   they are served in; the engine with the cell's settings;
2. warm-up of every program the window uses: a short request (the serve
   step and the slot reset), then one that reuses part of its last page
   (the copy-on-write page copy), then the cell's own traffic: for chat
   sessions ``warmup_s`` of the same schedule, for a closed loop the first
   wave until every request has its first token;
3. the window of ``seconds``, with a profiler trace of
   ``trace.seconds`` in its middle when ``--trace 1``;
4. the device's peak memory, then the engine is freed and the reference
   scores the served tokens.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, List, Optional

import jax
import numpy as np

import check
import devtrace
import program
import spec
import tails
import traffic
import work
from drive import LoadGen, run_closed, run_sessions


class CompileCounter:
    """Counts the backend compiles JAX reports."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@dataclasses.dataclass
class RunRecord:
    """What a metric's reader reads."""
    cell: spec.Cell
    drv: LoadGen
    origin: float  # host clock of the schedule's 0
    window: tuple  # (open, close) on the host clock
    setup_s: float
    chips: int
    peaks: dict
    model: work.Model
    window_delta: dict
    admitted_window: List[int]
    trace: Optional[devtrace.Reduced] = None
    traced_work: Optional[work.Work] = None
    notes: List[str] = dataclasses.field(default_factory=list)


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed of any size."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    for i, part in enumerate(_high_words(seed >> 32)):
        key = jax.random.fold_in(jax.random.fold_in(key, i), part)
    return key


def _high_words(x: int) -> List[int]:
    out = []
    while x:
        out.append(x & 0xFFFFFFFF)
        x >>= 32
    return out


def make_weights(ref, hf: dict, seed: int, sharding):
    fn = jax.jit(lambda k: ref.make_weights(k, hf), out_shardings=sharding)
    return jax.block_until_ready(fn(seed_key(seed)))


def _warm_programs(drv: LoadGen, eng, vocab: int, page: int) -> None:
    """Compile the serve step, the slot reset and the page copy."""
    first = (np.arange(2 * page + page // 2, dtype=np.int32) * 7919 + 11) % vocab
    second = first.copy()
    second[page + page // 4:] = (second[page + page // 4:] + 1) % vocab
    for p in (first, second):
        drv.submit(traffic.Req(-1, p, 2, None), drv.clock())
        while not eng.idle:
            drv.tick()
    eng.drop_prefix_cache()


def _admitted(drv: LoadGen, ticks: range) -> List[int]:
    before = sum(drv.ticks[i].delta["admissions"] for i in range(ticks.start))
    n = sum(drv.ticks[i].delta["admissions"] for i in ticks)
    return list(range(before, min(before + n, len(drv.tracked))))


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, log=sys.stderr,
             keep_trace: Optional[Path] = None,
             after: Optional[Callable] = None) -> dict:
    """One run.  ``after(run, ref, weights, sample)``, where given, is
    called once the reference has scored the sample (a tool's extra
    readings); its result is returned under ``"also"``."""
    s, hf = cell.settings, cell.config
    eng_cfg = s["engine"]
    chips = cell.chips
    kind = devices[0].device_kind
    peaks = work.peaks(kind) if devices[0].platform != "cpu" else None
    ref = check.reference_module(cell.reference)
    cfg = program.model_cfg(hf)
    mesh = program.make_mesh(chips)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(mesh, P())
    else:
        from jax.sharding import SingleDeviceSharding
        sharding = SingleDeviceSharding(devices[0])
    weights = make_weights(ref, hf, seed, sharding)
    program.check_layout(cfg, weights)
    eng = program.make_engine(weights, cfg, eng_cfg, mesh)
    counter = CompileCounter()
    drv = LoadGen(eng)
    _warm_programs(drv, eng, hf["vocab_size"], eng_cfg["page_size"])
    warmup_s = s.get("warmup_s", 0.0)
    sched = traffic.generate(cell.traffic, seed, vocab=hf["vocab_size"],
                             cache_len=eng_cfg["cache_len"],
                             warmup_s=warmup_s, seconds=seconds)
    trace_dir = cell.root / ".bench_trace" / cell.name
    tw = {"on": False, "done": False, "ticks": [None, None], "ann": None}
    tspec = s["trace"]

    def between():
        if not trace or tw["done"]:
            return
        now = drv.clock()
        if not tw["on"] and now >= t_open + tspec["start_s"]:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            tw["ann"] = jax.profiler.TraceAnnotation("bench.traced")
            tw["ann"].__enter__()
            tw["on"], tw["ticks"][0] = True, len(drv.ticks)
        elif tw["on"] and now >= tw_until():
            tw["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            tw["on"], tw["done"], tw["ticks"][1] = False, True, len(drv.ticks)

    def tw_until():
        return t_open + tspec["start_s"] + tspec["seconds"]

    if sched.kind == "sessions":
        feed = traffic.Sessions(sched.sessions)
        origin = drv.clock() + warmup_s
        run_sessions(drv, feed, origin, origin)
        # the window is the schedule's [0, seconds): a warm-up step that
        # ran past 0 is the window's first wait
        first_tick, t_open = len(drv.ticks), origin
        in_flight = [drv.in_flight]
        compiles0 = counter.n
        run_sessions(drv, feed, origin, origin + seconds, between)
        t_close = origin + seconds
    else:
        reqs = sched.requests
        for r in reqs[:sched.clients]:
            drv.submit(r, drv.clock())
        wave = drv.tracked[-sched.clients:]
        while not all(t.times for t in wave):
            drv.tick()
        first_tick, t_open = len(drv.ticks), drv.clock()
        in_flight = [drv.in_flight]
        origin = t_open
        compiles0 = counter.n
        run_closed(drv, reqs, t_open + seconds, sched.clients, between)
        t_close = drv.clock()
    if tw["on"]:  # a window shorter than the trace's end
        tw["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        tw["on"], tw["done"], tw["ticks"][1] = False, True, len(drv.ticks)
    in_flight.append(drv.in_flight)
    compiles = counter.n - compiles0
    traces = eng.stats["traces"]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices[:chips])
    window_ticks = range(first_tick, len(drv.ticks))
    delta = {k: sum(drv.ticks[i].delta[k] for i in window_ticks)
             for k in drv.ticks[0].delta}
    # free the program's state before the reference runs
    drv.eng = None
    del eng
    gc.collect()

    lo, hi = t_open, t_close
    served = [(t.req.prompt, list(t.request.out_tokens[:sum(
        1 for x in t.times if x <= hi)])) for t in drv.tracked
        if t.req.idx >= 0 and t.times and t.times[0] <= hi]
    served = [(p, o) for p, o in served if o]
    ck = s["check"]
    picked = check.sample(served, seed, ck["sample_tokens"], ck["max_requests"])
    t_ref = time.perf_counter()
    gap = (check.widest_gap(ref, weights, hf, picked, eng_cfg["cache_len"])
           if picked else float("inf"))
    ref_s = time.perf_counter() - t_ref

    model = work.Model.from_config(hf, eng_cfg["kv_dtype"])
    run = RunRecord(cell=cell, drv=drv, origin=origin, window=(lo, hi),
                    setup_s=t_open - t_start, chips=chips, peaks=peaks,
                    model=model, window_delta=delta,
                    admitted_window=_admitted(drv, window_ticks))
    also = after(run, ref, weights, picked) if after else None
    if trace and tw["done"]:
        events = devtrace.load(trace_dir)
        if keep_trace is not None:
            keep_trace.parent.mkdir(parents=True, exist_ok=True)
            keep_trace.write_text(json.dumps(devtrace.excerpt(events)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = devtrace.Reduced(events, step_module=tspec["step_module"],
                                     kernel=tspec["kernel"])
        works = work.step_work(model, drv, eng_cfg["prefill_chunk"])
        i0, i1 = tw["ticks"]
        run.traced_work = work.total(works[i0:i1])
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(m["name"], cell.root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # what the window attempted and what failed in it
    if sched.kind == "sessions":
        att = [t for t in drv.tracked if t.req.due is not None and lo <= t.due < hi]
        refused = [r for r in drv.refused
                   if r.due is not None and lo <= origin + r.due < hi]
    else:
        att = [t for t in drv.tracked if t.req.idx >= 0 and (
            lo <= t.submitted < hi or any(lo < x <= hi for x in t.times))]
        refused = list(drv.refused)
    failed = sum(t.failed for t in att) + len(refused)
    late = sorted(t.submitted - t.due for t in att)
    checks = {
        "logit_gap": {"value": gap, "limit": ck["max_gap"]},
        "traces": {"value": traces, "limit": 1},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    print(f"cell {cell.name} seed {seed}: window {hi - lo} s, "
          f"{len(window_ticks)} steps, {len(att)} requests attempted, "
          f"{failed} failed; set-up {t_open - t_start} s; requests in "
          f"flight at the window's open and close: {in_flight}", file=log)
    if late:
        print(f"generator lateness (submit minus due): median "
              f"{late[len(late) // 2]} s, max {late[-1]} s", file=log)
    if sched.kind == "sessions":
        print("time to first token, every sample (s): "
              f"{sorted(round(x, 3) for x in tails.ttft_s(run))}", file=log)
    print(f"reference: {len(picked)} requests, "
          f"{sum(len(o) for _, o in picked)} served tokens scored in "
          f"{ref_s} s", file=log)
    for note in run.notes:
        print(note, file=log)
    if run.traced_work is not None:
        w = run.traced_work
        print(f"traced window: {w.steps} steps, {w.tokens} tokens, "
              f"{w.emitted} emitted, unplaced prefill tokens {w.unplaced}",
              file=log)
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": len(att) + len(refused),
           "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops(),
                            "idle_gaps": run.trace.idle_gaps()}
    if also is not None:
        out["also"] = also
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=log)
    return out
