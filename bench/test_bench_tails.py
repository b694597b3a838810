"""Tails and rates are taken over every sample of the window, and the work
count comes from the served lengths."""
from __future__ import annotations

from types import SimpleNamespace as NS

import numpy as np
import pytest

import spec
import tails
import work
from traffic import Req


def tracked(due, times, prompt=10, failed=False, idx=0):
    req = Req(idx, np.zeros(prompt, np.int32), 8, due)
    r = NS(out_tokens=[0] * len(times), done=True,
           error=RuntimeError() if failed else None)
    t = NS(req=req, request=r, due=100.0 + due, submitted=100.0 + due,
           times=list(times))
    t.failed = failed
    return t


def run_of(ts, refused=()):
    return NS(window=(100.0, 110.0), origin=100.0,
              drv=NS(tracked=ts, refused=list(refused)))


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert tails.percentile(xs, 50) == 50
    assert tails.percentile(xs, 90) == 90
    assert tails.percentile(xs, 95) == 95
    assert tails.percentile([3.0], 95) == 3.0
    assert tails.percentile([], 50) is None


def test_ttft_counts_stalls_failures_and_refusals():
    ts = [tracked(1.0, [101.5, 101.6]),  # 0.5 s
          tracked(2.0, []),  # no first token: 110 - 102 = 8 s
          tracked(3.0, [103.2], failed=True),  # failed: 7 s
          tracked(-1.0, [100.5]),  # due before the window: not a sample
          tracked(9.0, [111.0])]  # first token after the close: 1 s
    refused = [Req(9, np.zeros(3, np.int32), 4, 4.0)]  # 110 - 104 = 6 s
    got = sorted(tails.ttft_s(run_of(ts, refused)))
    assert got == pytest.approx([0.5, 1.0, 6.0, 7.0, 8.0])


def test_gaps_and_tokens_are_those_of_the_window():
    ts = [tracked(0.0, [99.0, 100.5, 101.0, 111.0]),
          tracked(1.0, [102.0, 102.25, 102.5])]
    assert sorted(tails.gaps_s(run_of(ts))) == pytest.approx(
        [0.25, 0.25, 0.5, 1.5])
    assert tails.tokens_in_window(run_of(ts)) == 5


def test_work_counts_lengths_not_grids():
    m = work.Model(d=8, H=4, kvH=2, hd=2, ff=16, L=3, V=100, act_bytes=2,
                   kv_bytes_token=3 * 2 * 2 * 2 * 2)
    prompt = np.arange(20, dtype=np.int32)
    t = NS(req=Req(0, prompt, 4, None))
    # step 1: admitted, 8 prefill tokens; step 2: 8 more; step 3: the last
    # 4 and the hand-off token, which emits output 0; step 4: output 1
    ticks = [
        NS(delta={"packed_tokens": 8, "admissions": 1,
                  "prefix_tokens_reused": 0}, emitted=[]),
        NS(delta={"packed_tokens": 8, "admissions": 0,
                  "prefix_tokens_reused": 0}, emitted=[]),
        NS(delta={"packed_tokens": 5, "admissions": 0,
                  "prefix_tokens_reused": 0}, emitted=[(0, 0, 1)]),
        NS(delta={"packed_tokens": 1, "admissions": 0,
                  "prefix_tokens_reused": 0}, emitted=[(0, 1, 2)]),
    ]
    ws = work.step_work(m, NS(tracked=[t], ticks=ticks), chunk=8)
    per_key = 4 * m.H * m.hd * m.L  # attention FLOPs per key attended
    assert ws[0].attn_flops == per_key * sum(range(1, 9))
    assert ws[1].attn_flops == per_key * sum(range(9, 17))
    assert ws[2].attn_flops == per_key * (sum(range(17, 21)) + 21)
    assert ws[3].attn_flops == per_key * 22
    # K/V read once per step at the slot's length, plus writes and q/o
    qo = m.L * 2 * m.H * m.hd * m.act_bytes
    assert ws[3].attn_bytes == 22 * m.kv_bytes_token + m.kv_bytes_token + qo
    assert [w.emitted for w in ws] == [0, 0, 1, 1]
    assert all(w.unplaced == 0 for w in ws)
    tot = work.total(ws)
    assert tot.matmul_flops == 2 * m.layer_params * 22 + 2 * m.d * m.V * 2


def test_unknown_device_kind_is_refused():
    assert work.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("reused,admitted,want", [
    (30, [0, 1], 75.0),  # 30 of the 40 admitted prompt tokens
    (0, [0, 1], 0.0),  # the cache off, or broken: 0, not silence
    (0, [], None),  # nothing admitted: nothing to read
])
def test_prefix_hit_share_reads_a_cache_that_reuses_nothing(reused, admitted,
                                                            want):
    ts = [tracked(1.0, [101.5], prompt=20), tracked(2.0, [102.5], prompt=20)]
    run = NS(drv=NS(tracked=ts), admitted_window=admitted,
             window_delta={"prefix_tokens_reused": reused})
    assert spec.reader("prefix_hit_share.chat")(run) == want
