"""Where every Pallas kernel of this repo runs: compiled by Mosaic on a
TPU, interpreted everywhere else.

The choice is made here and nowhere else, from the platform the program
is lowered for (``jax.lax.platform_dependent``), not from a caller's
flag or from the process's default backend. So a serving program on the
chip never runs a kernel in interpret mode, CPU tests run the same
kernels through the interpreter, and a program cross-compiled for a
described TPU (tests/test_tpu_compile.py) takes the Mosaic kernel even
in a process whose default backend is the CPU.
"""
from __future__ import annotations

from typing import Callable

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel: Callable, *, name: str, **kw) -> Callable:
    """``pl.pallas_call(kernel, **kw)`` with ``interpret`` decided by the
    lowering platform (see module docstring). ``name`` is the kernel's
    one stable name: Mosaic's ``kernel_name``, and ``{"kernel": name}``
    in the custom call's ``kernel_metadata``, which a profiler trace's
    op text carries."""
    kw.update(name=name, metadata={"kernel": name})
    compiled = pl.pallas_call(kernel, interpret=False, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)

    def call(*args):
        return jax.lax.platform_dependent(
            *args, tpu=compiled, default=interpreted)

    return call
