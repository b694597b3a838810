"""Shared ``interpret`` default for every Pallas entry point.

A dependency-free leaf module so the kernel modules (flash_attention,
matmul, rmsnorm) can import it at the top level without a cycle through
``kernels.ops`` (which imports all of them); ``kernels.ops`` re-exports
``default_interpret`` as the public name.

Callers resolve the flag BEFORE a jit boundary and pass a concrete bool as
the static ``interpret`` argument, so the cached trace is keyed on the mode
it was built for.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """False iff the active backend is a real TPU (the kernels then compile
    through Mosaic); True everywhere else (CPU CI runs the kernels in
    interpret mode).  Nothing overrides it: on a TPU the kernels always
    compile, so a chip run can never measure the interpreter."""
    return jax.default_backend() != "tpu"
