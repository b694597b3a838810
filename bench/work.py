"""The work the traffic needed, counted from the served tokens.

Nothing here reads the kernel's grid, the block table's width or the
compiled program: a step's work is what its tokens need at their real
context lengths, so a kernel that skips empty pages raises its share
without making the count stale.

Per step, from the load generator's books:

- every emitted token is output ``i`` of a request with an ``L``-token
  prompt, computed at position ``L + i`` (the engine feeds the prompt's
  last token again at position ``L`` to produce output 0), so it attends
  ``L + i + 1`` keys;
- the step's other packed tokens are prefill.  Admission is in submission
  order (the engine's FIFO default), so the ``admissions`` delta names the
  admitted requests; each starts at the prefix it reused (the
  ``prefix_tokens_reused`` delta, split in proportion to the longest prefix
  each shares with an earlier prompt), and the step's prefill tokens are
  handed out in admission order, at most ``prefill_chunk`` per request.  A
  request's first output closes its prefill at ``L``.

Attention needs ``4 * ctx * heads * head_dim`` FLOPs per token and layer,
and reads each active slot's K/V once per step (``ctx`` of its last token
that step), writes its new K/V and reads q and writes o.  The matrix
products need ``2 * params`` FLOPs per token for the layers and the LM
head's ``2 * d * V`` per emitted token.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"

# bytes per stored K or V element, and per-entry scale bytes, by pool dtype
KV_ELEM = {"bfloat16": (2, 0), "float32": (4, 0), "int8": (1, 4)}


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table["chips"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS.name}")
    return table["chips"][device_kind]


@dataclasses.dataclass
class Model:
    d: int
    H: int
    kvH: int
    hd: int
    ff: int
    L: int
    V: int
    act_bytes: int
    kv_bytes_token: int  # K and V of one token over all layers

    @classmethod
    def from_config(cls, hf: dict, kv_dtype: str) -> "Model":
        d, H = hf["hidden_size"], hf["num_attention_heads"]
        hd = hf.get("head_dim", d // H)
        kvH, L = hf["num_key_value_heads"], hf["num_hidden_layers"]
        elem, scale = KV_ELEM[kv_dtype]
        return cls(d=d, H=H, kvH=kvH, hd=hd, ff=hf["intermediate_size"], L=L,
                   V=hf["vocab_size"], act_bytes=2,
                   kv_bytes_token=L * 2 * kvH * (hd * elem + scale))

    @property
    def layer_params(self) -> int:
        attn = self.d * (self.H + 2 * self.kvH) * self.hd + self.H * self.hd * self.d
        return self.L * (attn + 3 * self.d * self.ff)


@dataclasses.dataclass
class Work:
    steps: int = 0
    tokens: int = 0
    emitted: int = 0
    matmul_flops: float = 0.0
    attn_flops: float = 0.0
    attn_bytes: float = 0.0
    unplaced: int = 0  # prefill tokens the books could not place

    def add(self, other: "Work") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))


def _shared_prefix(prompt: np.ndarray, earlier: List[np.ndarray]) -> int:
    best = 0
    for p in earlier:
        n = min(len(p), len(prompt))
        neq = np.flatnonzero(p[:n] != prompt[:n])
        best = max(best, int(neq[0]) if neq.size else n)
    return best


def step_work(model: Model, drv, chunk: int) -> List[Work]:
    """Work of every recorded step of ``drv`` (a ``drive.LoadGen``), in
    order."""
    tracked = drv.tracked
    prompts = [t.req.prompt for t in tracked]
    fill: Dict[int, int] = {}
    prefilling: List[int] = []  # admitted, no output yet, in admission order
    admitted = 0
    out = []
    for rec in drv.ticks:
        w = Work(steps=1, tokens=rec.delta["packed_tokens"])
        new = list(range(admitted, min(admitted + rec.delta["admissions"],
                                       len(tracked))))
        admitted += rec.delta["admissions"]
        if new:
            est = [min(_shared_prefix(prompts[i], prompts[:i]),
                       len(prompts[i]) - 1) for i in new]
            reused = rec.delta["prefix_tokens_reused"]
            tot = sum(est)
            for i, e in zip(new, est):
                share = reused * e / tot if tot else reused / len(new)
                fill[i] = min(int(round(share)), len(prompts[i]))
            prefilling += new
        ctx_of_slot: Dict[int, int] = {}
        pre = w.tokens
        for i, had, n in rec.emitted:
            L = len(prompts[i])
            if had == 0 and i in fill:  # prefill closed this step
                c = max(L - fill[i], 0)
                _prefill(w, model, fill[i], c, ctx_of_slot, i)
                pre -= c
                fill[i] = L
                prefilling.remove(i)
            for k in range(had, n):
                ctx = L + k + 1
                w.attn_flops += 4.0 * ctx * model.H * model.hd * model.L
                ctx_of_slot[i] = max(ctx_of_slot.get(i, 0), ctx)
            w.emitted += n - had
            pre -= n - had
        for i in list(prefilling):
            if pre <= 0:
                break
            c = min(chunk, len(prompts[i]) - 1 - fill[i], pre)
            if c > 0:
                _prefill(w, model, fill[i], c, ctx_of_slot, i)
                fill[i] += c
                pre -= c
        w.unplaced = max(pre, 0)
        w.attn_bytes = (sum(ctx_of_slot.values()) * model.kv_bytes_token
                        + w.tokens * model.kv_bytes_token
                        + w.tokens * model.L * 2 * model.H * model.hd
                        * model.act_bytes)
        w.matmul_flops = (2.0 * model.layer_params * w.tokens
                          + 2.0 * model.d * model.V * w.emitted)
        out.append(w)
    return out


def _prefill(w: Work, model: Model, start: int, c: int,
             ctx_of_slot: Dict[int, int], i: int) -> None:
    if c <= 0:
        return
    # positions start .. start+c-1 attend start+1 .. start+c keys
    ctx_sum = c * start + c * (c + 1) // 2
    w.attn_flops += 4.0 * ctx_sum * model.H * model.hd * model.L
    ctx_of_slot[i] = max(ctx_of_slot.get(i, 0), start + c)


def total(works: List[Work]) -> Work:
    t = Work()
    for w in works:
        t.add(w)
    return t
