"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the
requests the window served, drawn from the seed and always holding the
one with the most served tokens, goes through the plain float32
reference: once over each prompt with its served tokens, teacher-forced.
Each served token is scored by the gap by which its reference logit lies
below the reference's best at that position, in standard deviations of
the reference's logits at that position (so one limit holds at any width
and vocabulary); the widest gap over the sample is the number compared.
Tokens are greedy, so a served token that is not the reference's top
choice is one the program's rounding moved, and a wide gap is one it
could not have moved.

The engine computes output ``i`` of an ``L``-token prompt at position
``L + i`` with the prompt's last token fed again at ``L``, so the sequence
the reference reads is ``prompt + [prompt[-1]] + served[:-1]`` and output
``i`` is read at row ``L + i``.  A plain decode reads it at row ``L - 1 +
i`` of ``prompt + served[:-1]``: ``handoff=False`` scores that alignment,
which ``bench/control.py`` reports beside the other.

A control swaps the reference's float32 products for float8 ones and
scores, at the same rows, the token the float8 model ranks first.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def reference_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"reference_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(served: List[tuple], seed: int, tokens: int, max_requests: int
           ) -> List[tuple]:
    """``served``: (prompt, out tokens) of every request with output.  The
    one with the most output first, then others in a seeded order until
    ``tokens`` output tokens or ``max_requests`` requests are in."""
    if not served:
        return []
    order = sorted(range(len(served)), key=lambda i: -len(served[i][1]))
    rest = order[1:]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xc4ec]))
    rest = [rest[j] for j in rng.permutation(len(rest))]
    out, n = [], 0
    for i in [order[0]] + rest:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(served[i])
        n += len(served[i][1])
    return out


@functools.lru_cache(maxsize=None)
def _scorer(ref, hf_items: tuple, control: bool):
    hf = dict(hf_items)

    @jax.jit
    def score(w, seq, rows, served):
        logits = ref.forward_rows(w, hf, seq, rows)
        best = jnp.max(logits, -1)
        sd = jnp.std(logits, -1)
        if control:
            low = ref.forward_rows(w, hf, seq, rows, matmul=ref.fp8_matmul)
            served = jnp.argmax(low, -1)
        got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        return (best - got) / sd

    return score


def widest_gap(ref, weights, hf: dict, requests: List[tuple], cache_len: int,
               control: bool = False, handoff: bool = True) -> float:
    """Widest gap over every served token of ``requests`` (or, with
    ``control``, over the tokens the float8 reference ranks first).
    Without ``handoff``, output ``i`` is read at row ``L - 1 + i`` of
    ``prompt + served[:-1]``, as a plain decode computes it."""
    score = _scorer(ref, tuple(sorted((k, v) for k, v in hf.items()
                                      if not isinstance(v, (list, dict)))),
                    control)
    worst = 0.0
    for prompt, out in requests:
        L, n = len(prompt), len(out)
        seq = np.zeros(cache_len, np.int32)
        seq[:L] = prompt
        first = L if handoff else L - 1
        if handoff:
            seq[L] = prompt[-1]
        seq[first + 1:first + n] = out[:-1]
        rows = np.zeros(cache_len, np.int32)
        rows[:n] = first + np.arange(n)
        tok = np.zeros(cache_len, np.int32)
        tok[:n] = out
        gap = np.asarray(score(weights, seq, rows, tok))[:n]
        worst = max(worst, float(gap.max()))
    return worst
