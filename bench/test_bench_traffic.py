"""The traffic generator: deterministic from the seed, the same work for
every seed, and chat sessions whose turns follow the served answers, so
their prefixes really are shared."""
from __future__ import annotations

import json
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
import traffic

HERE = Path(__file__).resolve().parent
CHAT = json.loads((HERE / "traffic" / "chat.json").read_text())
CLOSED = json.loads((HERE / "traffic" / "decode-closed.json").read_text())
BIG = 2 ** 31 + 4_000_000_017


def gen(t, seed, seconds=40.0):
    return traffic.generate(t, seed, vocab=151936, cache_len=2048,
                            warmup_s=20.0, seconds=seconds)


def shapes(s):
    return [(x.start, len(x.system), [len(u) for u in x.users], x.answers,
             x.thinks) for x in s.sessions]


def test_same_seed_same_inputs():
    a, b = gen(CHAT, BIG), gen(CHAT, BIG)
    assert shapes(a) == shapes(b) and a.sessions
    for x, y in zip(a.sessions, b.sessions):
        assert np.array_equal(x.system, y.system)
        assert all(np.array_equal(u, v) for u, v in zip(x.users, y.users))
    a, b = gen(CLOSED, BIG), gen(CLOSED, BIG)
    assert len(a.requests) == len(b.requests) > 0
    for x, y in zip(a.requests, b.requests):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_tokens, x.due) == (y.max_tokens, y.due)


def test_seeds_differ_in_tokens_not_in_work():
    a, b = gen(CLOSED, 1), gen(CLOSED, 2)
    C = CLOSED["clients"]
    for w in range(CLOSED["waves"]):
        wa = a.requests[w * C:(w + 1) * C]
        wb = b.requests[w * C:(w + 1) * C]
        assert (sorted((len(r.prompt), r.max_tokens) for r in wa)
                == sorted((len(r.prompt), r.max_tokens) for r in wb))
    assert not np.array_equal(a.requests[0].prompt[:64],
                              b.requests[0].prompt[:64])
    # chat: the very same sessions, starts and think times; other tokens
    sa, sb = gen(CHAT, 1, 600.0), gen(CHAT, 2, 600.0)
    assert shapes(sa) == shapes(sb)
    assert not np.array_equal(sa.sessions[0].system, sb.sessions[0].system)


def serve_all(s, latency=3.0, horizon=1e9):
    """Answer every turn ``latency`` s after it falls due, with made-up
    tokens; returns the turns in the order they fell due and the times
    they were answered."""
    feed, turns, done = traffic.Sessions(s.sessions), [], {}
    rng = np.random.default_rng(0)
    while feed.next_due() is not None and feed.next_due() < horizon:
        for r in feed.pop_due(feed.next_due()):
            served = rng.integers(0, 151936, r.max_tokens).tolist()
            done[r.idx] = (served, r.due + latency)
            turns.append(r)
            feed.finished(r, *done[r.idx])
    return turns, done


def test_chat_prefixes_are_shared():
    s = gen(CHAT, 7)
    turns, done = serve_all(s)
    by_session = {}
    for r in turns:
        by_session.setdefault(r.session, []).append(r)
    multi = [rs for rs in by_session.values() if len(rs) > 1]
    assert multi
    for rs in multi:
        for a, b in zip(rs, rs[1:]):
            served, at = done[a.idx]
            ses = s.sessions[a.session]
            # history: the last prompt, the answer as served, a new message
            assert b.turn == a.turn + 1
            assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
            assert b.prompt[len(a.prompt):len(a.prompt) + len(served)].tolist() == served
            assert np.array_equal(b.prompt[len(a.prompt) + len(served):],
                                  ses.users[b.turn])
            # due a think time after the answer, not after the last due
            assert b.due == pytest.approx(at + ses.thinks[a.turn])
    # different sessions share system prompts of 256-1024 tokens
    heads = Counter(tuple(r.prompt[:256]) for r in turns)
    assert max(heads.values()) > len(by_session) / 4
    assert all(len(r.prompt) + r.max_tokens <= 2048 for r in turns)
    # the backlog's sessions all start as the warm-up opens
    starts = [x.start for x in s.sessions]
    assert starts == sorted(starts) and -20.0 <= starts[0] and starts[-1] < 40.0
    backlog = CHAT["arrivals"]["rate_per_s"] * CHAT["arrivals"]["backlog_s"]
    assert 0.5 * backlog < starts.count(-20.0) < 2 * backlog


def test_no_turn_falls_due_before_the_last_is_answered():
    s = gen(CHAT, 9)
    feed = traffic.Sessions(s.sessions)
    first = feed.pop_due(1e9)
    assert sorted(r.session for r in first) == list(range(len(s.sessions)))
    assert all(r.turn == 0 for r in first) and feed.next_due() is None
    r = next(r for r in first if len(s.sessions[r.session].answers) > 1)
    feed.finished(r, [5] * r.max_tokens, 100.0)
    (nxt,) = feed.pop_due(1e9)
    assert (nxt.session, nxt.turn) == (r.session, 1)
    assert len(nxt.prompt) == len(r.prompt) + r.max_tokens + len(
        s.sessions[r.session].users[1])


def test_think_time_is_capped():
    rng = np.random.default_rng(3)
    xs = [traffic.draw(rng, CHAT["think_s"]) for _ in range(20000)]
    assert max(xs) == CHAT["think_s"]["max"]
    assert np.mean(xs) == pytest.approx(CHAT["think_s"]["mean"], rel=0.05)


def test_seed_keeps_every_bit():
    a = harness.seed_key(BIG)
    b = harness.seed_key(BIG & 0xFFFFFFFF)
    c = harness.seed_key(BIG + 2 ** 32)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(c))
    assert np.array_equal(jax.random.key_data(harness.seed_key(5)),
                          jax.random.key_data(jax.random.PRNGKey(5)))
