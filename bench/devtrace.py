"""Reduction of a profiler trace to device times.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a flat
list of events ``{"plane", "line", "name", "start_ns", "dur_ns"}``; every
other function here reads only that list, so a small recorded trace in the
same form checks the arithmetic on the CPU.

Device planes are named ``/device:TPU:<n>``.  On them, ops are the events
of the ``XLA Ops`` line, named by their HLO text (``%name = ...``, of
which the name is kept), and program runs those of the ``XLA Modules``
line.  Ops nest (a scan's ``while`` holds the layer's ops), so busy time
is a union and an op's own time excludes the ops inside it.  Collectives
are taken from the ``XLA Ops`` line and, where they run asynchronously,
from the ``Async XLA Ops`` line (start to done).  The serve step and the
attention kernel are found by the names the program gives them today, which each cell's file states
(``trace.step_module``, ``trace.kernel``); the benchmark's own host spans
are the events named ``bench.*``.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS, MODULES, ASYNC = "XLA Ops", "XLA Modules", "Async XLA Ops"
OP_NAME = re.compile(r"%?([^\s=]+)")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)


def load(trace_dir: Path) -> List[dict]:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        keep_all = DEVICE.match(plane.name) is not None
        for line in plane.lines:
            for e in line.events:
                if keep_all or e.name.startswith("bench."):
                    events.append({"plane": plane.name, "line": line.name,
                                   "name": e.name, "start_ns": e.start_ns,
                                   "dur_ns": e.duration_ns})
    return events


def excerpt(events: List[dict], span_ms: float = 400.0) -> dict:
    """A small copy of a trace: the count and the busiest names of every
    (plane, line), and every event of the first ``span_ms`` of the traced
    window, with the window's span cut to that length."""
    lines: Dict[str, dict] = {}
    for e in events:
        d = lines.setdefault(f"{e['plane']} | {e['line']}", {"n": 0, "names": {}})
        d["n"] += 1
        d["names"][e["name"]] = d["names"].get(e["name"], 0) + e["dur_ns"]
    for d in lines.values():
        d["names"] = sorted(d["names"].items(), key=lambda kv: -kv[1])[:25]
    w = [e for e in events if e["name"] == "bench.traced"]
    lo = w[0]["start_ns"] if w else min(e["start_ns"] for e in events)
    hi = lo + span_ms * 1e6
    cut = [dict(e) for e in events if lo <= e["start_ns"] < hi]
    for e in cut:
        if e["name"] == "bench.traced":
            e["dur_ns"] = hi - lo
    return {"lines": lines, "events": cut}


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _length(iv) -> float:
    return sum(b - a for a, b in iv)


def _own_times(named) -> Dict[str, float]:
    """{name: time not covered by ops nested inside it}; ops nest or do
    not overlap."""
    own: Dict[str, float] = {}
    stack: List[list] = []  # [end, name, own time so far]

    def close(until):
        while stack and stack[-1][0] <= until:
            end, name, t = stack.pop()
            own[name] = own.get(name, 0.0) + t

    for (a, b), name in sorted(named, key=lambda x: (x[0][0], -x[0][1])):
        close(a)
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, name, b - a])
    close(float("inf"))
    return own


class Reduced:
    """Device times of one traced window, per device, in seconds."""

    def __init__(self, events: List[dict], *, step_module: str, kernel: str):
        spans = [e for e in events if e["name"] == "bench.traced"]
        if not spans:
            raise ValueError("trace holds no bench.traced span")
        w = spans[0]
        self.lo, self.hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
        self.window_s = (self.hi - self.lo) * 1e-9
        self.host = [e for e in events if e["name"].startswith("bench.")
                     and e["name"] != "bench.traced"]
        step_re, kern_re = re.compile(step_module), re.compile(kernel)
        self.devices: Dict[str, dict] = {}
        for e in events:
            if not DEVICE.match(e["plane"]) or e["line"] not in (
                    OPS, MODULES, ASYNC):
                continue
            a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
            if b <= self.lo or a >= self.hi:
                continue
            d = self.devices.setdefault(e["plane"], {
                "ops": [], "steps": [], "kernel": [], "collective": [],
                "named": []})
            iv = (max(a, self.lo), min(b, self.hi))
            if e["line"] == MODULES:
                if step_re.search(e["name"]):
                    d["steps"].append(iv)
                continue
            name = OP_NAME.match(e["name"]).group(1)
            if e["line"] == ASYNC:
                if COLLECTIVE.search(name):
                    d["collective"].append(iv)
                continue
            d["ops"].append(iv)
            d["named"].append((iv, name))
            if kern_re.search(name):
                d["kernel"].append(iv)
            if COLLECTIVE.search(name):
                d["collective"].append(iv)
        if not self.devices:
            raise ValueError("trace holds no device ops in the window")

    def _mean(self, f) -> float:
        return sum(f(d) for d in self.devices.values()) / len(self.devices)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return self._mean(lambda d: _length(_union(d["ops"]))) * 1e-9

    @property
    def steps(self) -> int:
        return max(len(d["steps"]) for d in self.devices.values())

    @property
    def step_s(self) -> float:
        """Device time of the serve-step program, per device."""
        return self._mean(lambda d: _length(d["steps"])) * 1e-9

    @property
    def kernel_s(self) -> float:
        return self._mean(lambda d: _length(d["kernel"])) * 1e-9

    @property
    def collective_s(self) -> float:
        return self._mean(lambda d: _length(_union(d["collective"]))) * 1e-9

    @property
    def collective_exposed_s(self) -> float:
        """Collective time during which no other op ran on that device
        (ops that hold the collective, such as a scan's loop, aside)."""
        def exposed(d):
            coll = _union(d["collective"])
            tot = 0.0
            for a, b in coll:
                beside = [(x, y) for x, y in d["ops"]
                          if not (x <= a and y >= b)
                          and not any(p <= x and y <= q for p, q in coll)]
                tot += (b - a) - _length(_union(_clip(beside, a, b)))
            return tot
        return self._mean(exposed) * 1e-9

    def device_ops(self, n: int = 10) -> List[list]:
        """The ops with the most own time (time inside no nested op),
        averaged over the devices."""
        tot: Dict[str, float] = {}
        for d in self.devices.values():
            for k, v in _own_times(d["named"]).items():
                tot[k] = tot.get(k, 0.0) + v / len(self.devices)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest gaps between ops on the first device, each named by
        the innermost benchmark span on the host that holds its middle."""
        first = self.devices[min(self.devices, key=lambda p: int(
            DEVICE.match(p).group(1)))]
        busy = _union(first["ops"])
        gaps, t = [], self.lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < self.hi:
            gaps.append((t, self.hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._span_at((a + b) / 2), (b - a) * 1e-9]
                for a, b in gaps[:n]]

    def _span_at(self, t: float) -> str:
        best: Optional[dict] = None
        for e in self.host:
            if e["start_ns"] <= t <= e["start_ns"] + e["dur_ns"]:
                if best is None or e["dur_ns"] < best["dur_ns"]:
                    best = e
        return best["name"] if best is not None else "between spans"
