"""Fixture: jitted programs invoked with Python scalars (rel=serve/...).

Line numbers asserted exactly by tests/test_analysis.py; edit with care.
"""
import numpy as np


class FakeEngine:
    def tick(self, params, state, tokens, page):
        state = self._ragged_step(params, state, len(tokens))  # VIOLATION 10
        data, state = self._gather_page(state, page)  # VIOLATION line 11:
        # bare page id bakes into the trace
        state = self._insert_page(state, data, np.int32(page))  # wrapped: OK
        return state
