"""The seed (pre-paging) serving engine, kept verbatim as the correctness
and throughput baseline.

Limitations that motivated the rebuild in ``engine.py``: prompts are
prefilled one slot at a time with batch-1 forwards (one compile per distinct
prompt length, sequential host round-trips), every slot pays ``cache_len``
KV regardless of sequence length, and positions are lock-step across slots
(shared ``k_pos``/``pos``) so only equal-length prompt waves decode
correctly.  Tests pin the paged engine token-for-token against this engine
on equal-length traffic.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelCfg
from repro.models import model as M
from repro.serve.handle import Request  # noqa: F401  (moved; re-exported)


class ReferenceEngine:
    def __init__(self, params, cfg: ModelCfg, *, batch_size: int = 4,
                 cache_len: int = 256, greedy: bool = True):
        self.params = params
        self.cfg = cfg
        self.B = batch_size
        self.cache_len = cache_len
        self._decode = jax.jit(
            lambda p, s, t: M.decode_step(p, cfg, s, t))
        self.queue: deque = deque()
        self.slots: List[Optional[Request]] = [None] * batch_size
        self._uid = 0

    def submit(self, prompt, max_tokens: int = 16, eos_id=None) -> int:
        self._uid += 1
        self.queue.append(Request(self._uid, np.asarray(prompt, np.int32),
                                  max_tokens, eos_id))
        return self._uid

    # -- internals --------------------------------------------------------
    def _fill_slots(self, state, last_tok):
        """Prefill queued requests into free slots (one at a time: per-slot
        prefill uses a batch-1 forward and writes that slot's cache rows)."""
        for b in range(self.B):
            if self.slots[b] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            self.slots[b] = req
            one = M.init_decode_state(self.params, self.cfg, 1, self.cache_len)
            one = M.prefill(self.params, self.cfg, one, req.prompt[None, :])
            state = _write_slot(state, one, b)
            last_tok = last_tok.at[b, 0].set(int(req.prompt[-1]))
        return state, last_tok

    def run(self, max_ticks: int = 256) -> Dict[int, List[int]]:
        """Drain the queue; returns {uid: generated tokens}."""
        state = M.init_decode_state(self.params, self.cfg, self.B,
                                    self.cache_len)
        last_tok = jnp.zeros((self.B, 1), jnp.int32)
        results: Dict[int, List[int]] = {}
        for _ in range(max_ticks):
            if all(s is None for s in self.slots) and not self.queue:
                break
            state, last_tok = self._fill_slots(state, last_tok)
            logits, state = self._decode(self.params, state, last_tok)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            nxt_host = np.asarray(nxt)
            for b, req in enumerate(self.slots):
                if req is None:
                    continue
                tok = int(nxt_host[b])
                req.out_tokens.append(tok)
                if (len(req.out_tokens) >= req.max_tokens
                        or (req.eos_id is not None and tok == req.eos_id)):
                    results[req.uid] = req.out_tokens
                    self.slots[b] = None
                else:
                    last_tok = last_tok.at[b, 0].set(tok)
        for req in self.slots:  # drain partials on tick budget exhaustion
            if req is not None:
                results[req.uid] = req.out_tokens
        return results


def _write_slot(state, one, b: int):
    """Copy a batch-1 decode state into slot ``b`` of the pooled state.

    Positions are lock-step across slots (k_pos is shared per layer), so the
    engine admits equal-length prompt waves; per-slot position tracking
    lives in the paged engine (serve/engine.py).  Recurrent states are
    per-batch-row and copy cleanly.
    """
    flat_p, treedef = jax.tree_util.tree_flatten_with_path(state)
    flat_o = [l for _, l in jax.tree_util.tree_flatten_with_path(one)[0]]
    out = []
    for (path, pl), sl in zip(flat_p, flat_o):
        name = None
        for p in reversed(path):
            if isinstance(p, jax.tree_util.DictKey):
                name = p.key
                break
        if pl.ndim == sl.ndim and pl.shape == sl.shape and pl.ndim == 0:
            out.append(jnp.maximum(pl, sl))  # scalar pos: lock-step max
        elif name == "k_pos":
            out.append(sl)  # shared slot positions (lock-step)
        else:
            # batch dim is the first dim whose size differs (pool B vs 1)
            axis = next((i for i, (a, c) in enumerate(zip(pl.shape, sl.shape))
                         if a != c), None)
            if axis is None:
                out.append(sl)
            else:
                out.append(jax.lax.dynamic_update_slice_in_dim(
                    pl, sl.astype(pl.dtype), b, axis))
    return jax.tree_util.tree_unflatten(treedef, out)
