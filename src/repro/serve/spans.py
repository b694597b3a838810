"""The serving engine's host spans and its one host clock.

Spans are ``jax.profiler`` annotations: while a profiler session is active
they are written into its trace, on the clock of the device's events, so a
reduction of the trace can name what the host was doing in each of the
device's idle gaps.  With no session active an annotation is an inactive
TraceMe and records nothing.

Inside each ``TICK`` (one ``ServeEngine.tick()``, a step annotation
numbered by the engine's tick counter), in order: ``ADMIT`` (admission
rounds and preemption), ``PACK`` (building the pack's arrays),
``DISPATCH`` (enqueueing the serve step), ``WAIT`` (until its greedy
tokens are ready), ``FETCH`` (their copy to the host: one int32 per logits
row) and ``SAMPLE`` (sampling and booking, speculative verify and
rollback; a row drawn at a temperature copies its logits here).
``MOVERS`` holds the tier traffic a round applies to device state (page
gathers and inserts).
"""
from __future__ import annotations

import time

import jax

TICK = "serve.tick"
ADMIT = "serve.admit"
MOVERS = "serve.movers"
PACK = "serve.pack"
DISPATCH = "serve.dispatch"
WAIT = "serve.wait"
FETCH = "serve.fetch"
SAMPLE = "serve.sample"

span = jax.profiler.TraceAnnotation
step = jax.profiler.StepTraceAnnotation


def now() -> float:
    """The host clock of the request stamps and the engine's logs."""
    # servelint: ignore[hot-nondeterminism] — measurement-only: stamps and logs read it, control flow never does
    return time.perf_counter()
