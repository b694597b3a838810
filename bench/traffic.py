"""The one traffic generator: reads a traffic file's parameters.

Two kinds of mix, each made from a seed:

- ``sessions`` (chat): sessions start as a Poisson process at
  ``arrivals.rate_per_s``, and each has a system prompt drawn by Zipf from
  a pool and 1 to ``turns.max`` turns.  A session's first turn is due at
  its start; turn k+1 is due a think time after turn k's answer is
  complete, and its prompt is turn k's prompt, the answer as served and a
  new user message (``Sessions`` hands the turns out as they fall due).  A
  session ends where prompt plus answer would pass the cell's
  ``cache_len``.  Sessions start with the warm-up, so its traffic builds
  the window's sessions and fills the cache; those that would have
  started in the ``arrivals.backlog_s`` seconds before it all start as it
  opens, so that the window finds sessions at every turn.
- ``closed`` (closed loop): ``clients`` clients, each sending its next
  unshared request when its last one completes.  Requests come in waves
  of one per client; an answer's range ends where prompt plus answer
  would pass ``cache_len``.

The sizes, the gaps between session starts and the think times are drawn
once from the file's ``base_seed``, so every seed offers the same work;
the run's ``--seed`` draws every token (and reorders the closed loop's
requests within each wave).  The same seed gives the same inputs.

Length specs: ``{"dist": "uniform", "min", "max"}``,
``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
``{"dist": "geometric", "mean", "min", "max"}``, ``{"dist": "fixed",
"value"}``; think times ``{"dist": "exponential", "mean", "max"}``.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    idx: int  # order in the schedule
    prompt: np.ndarray  # int32 token ids
    max_tokens: int
    due: Optional[float]  # schedule clock (s, window opens at 0); None: closed loop
    session: int = 0
    turn: int = 0


@dataclasses.dataclass
class Session:
    idx: int
    start: float  # schedule clock at which its first turn is due
    system: np.ndarray  # the system prompt's tokens
    users: List[np.ndarray]  # each turn's new user message
    answers: List[int]  # each turn's max_tokens
    thinks: List[float]  # the think time after each turn's answer


@dataclasses.dataclass
class Schedule:
    kind: str  # "sessions" | "closed"
    requests: List[Req] = dataclasses.field(default_factory=list)  # closed: in send order
    sessions: List[Session] = dataclasses.field(default_factory=list)  # by start
    clients: int = 0


class Sessions:
    """The turns of chat sessions, handed out as they fall due."""

    def __init__(self, sessions: List[Session]):
        self.sessions = sessions
        # (due, session, turn, the history the turn's message follows)
        self._due = [(s.start, s.idx, 0, s.system) for s in sessions]
        heapq.heapify(self._due)
        self._n = 0

    def next_due(self) -> Optional[float]:
        return self._due[0][0] if self._due else None

    def pop_due(self, now: float) -> List[Req]:
        """Every turn due by ``now`` (schedule clock), in due order."""
        out = []
        while self._due and self._due[0][0] <= now:
            due, si, k, history = heapq.heappop(self._due)
            s = self.sessions[si]
            out.append(Req(self._n, np.concatenate([history, s.users[k]]),
                           s.answers[k], due, session=si, turn=k))
            self._n += 1
        return out

    def finished(self, req: Req, served: List[int], at: float) -> None:
        """Turn ``req`` was answered with ``served`` at ``at`` (schedule
        clock): the session's next turn falls due a think time later."""
        s = self.sessions[req.session]
        k = req.turn + 1
        if k < len(s.answers):
            history = np.concatenate([req.prompt, np.asarray(served, np.int32)])
            heapq.heappush(self._due, (at + s.thinks[req.turn], s.idx, k, history))


def draw(rng: np.random.Generator, spec: dict) -> float:
    dist = spec["dist"]
    if dist == "fixed":
        return spec["value"]
    if dist == "uniform":
        return int(rng.integers(spec["min"], spec["max"] + 1))
    if dist == "lognormal":
        v = round(spec["median"] * math.exp(spec["sigma"] * rng.standard_normal()))
    elif dist == "geometric":
        v = spec["min"] - 1 + int(rng.geometric(1.0 / (spec["mean"] - spec["min"] + 1)))
    elif dist == "exponential":
        return min(float(rng.exponential(spec["mean"])),
                   spec.get("max", math.inf))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return int(min(max(v, spec["min"]), spec["max"]))


def _rngs(base_seed: int, seed: int):
    return (np.random.default_rng(base_seed),
            np.random.default_rng(np.random.SeedSequence([seed, 0x7a3f])))


def generate(traffic: dict, seed: int, *, vocab: int, cache_len: int,
             warmup_s: float, seconds: float) -> Schedule:
    if traffic["kind"] == "sessions":
        return _sessions(traffic, seed, vocab, cache_len, warmup_s, seconds)
    if traffic["kind"] == "closed":
        return _closed(traffic, seed, vocab, cache_len)
    raise ValueError(f"unknown traffic kind {traffic['kind']!r}")


def _sessions(t, seed, vocab, cache_len, warmup_s, seconds) -> Schedule:
    base, run = _rngs(t["base_seed"], seed)
    sp = t["system_prompts"]
    ranks = np.arange(1, sp["count"] + 1, dtype=np.float64)
    zipf = ranks ** -sp["zipf_s"]
    zipf /= zipf.sum()
    sys_lens = [draw(base, sp["len"]) for _ in range(sp["count"])]
    rate = t["arrivals"]["rate_per_s"]
    backlog = t["arrivals"].get("backlog_s", 0.0)
    n = int(math.ceil(rate * (backlog + warmup_s + seconds) * 1.5)) + 8
    shapes = []
    for _ in range(n):
        turns = int(draw(base, t["turns"]))
        shapes.append(dict(
            sys=int(base.choice(sp["count"], p=zipf)),
            user=[draw(base, t["user_len"]) for _ in range(turns)],
            answer=[draw(base, t["answer_len"]) for _ in range(turns)],
            think=[draw(base, t["think_s"]) for _ in range(turns)]))
    gaps = base.exponential(1.0 / rate, n)
    sys_toks = [run.integers(0, vocab, k, dtype=np.int32) for k in sys_lens]
    sessions = []
    start = -warmup_s - backlog
    for shape, gap in zip(shapes, gaps):
        start += gap
        if start >= seconds:
            break
        length, users, answers = sys_lens[shape["sys"]], [], []
        for u, a in zip(shape["user"], shape["answer"]):
            if length + u + a > cache_len:
                break
            users.append(run.integers(0, vocab, u, dtype=np.int32))
            answers.append(a)
            length += u + a
        if answers:
            sessions.append(Session(len(sessions), max(start, -warmup_s),
                                    sys_toks[shape["sys"]], users, answers,
                                    shape["think"][:len(answers)]))
    return Schedule("sessions", sessions=sessions)


def _closed(t, seed, vocab, cache_len) -> Schedule:
    base, run = _rngs(t["base_seed"], seed)
    C = t["clients"]
    reqs = []
    for w in range(t["waves"]):
        shapes = []
        for _ in range(C):
            p = draw(base, t["prompt_len"])
            # the answer's range ends where prompt plus answer fill the cache
            room = dict(t["answer_len"])
            room["max"] = min(room["max"], cache_len - p)
            if room["max"] < room["min"]:
                raise ValueError(f"a {p}-token prompt leaves no room for an "
                                 f"answer in cache_len {cache_len}")
            shapes.append((p, draw(base, room)))
        for j in run.permutation(C):
            p, a = shapes[j]
            reqs.append(Req(len(reqs), run.integers(0, vocab, p, dtype=np.int32),
                            a, None, session=len(reqs)))
    return Schedule("closed", requests=reqs, clients=C)
