"""Named host spans at the program's layer boundaries.

Each span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``: it
lands in the profiler's trace beside the device's programs and ops, with
its keyword arguments as the event's stats.  The profiler's trace is the
only record; profile the process (``jax.profiler.trace`` or
``jax.profiler.start_server``) to get them.  With no profiler running a
span costs about a microsecond.

    with spans.span("fl.flush.copy", nbytes=n):
        ...

Compute an argument that costs something (a byte count) only when
``enabled()``.
"""
from __future__ import annotations

import jax
import numpy as np


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A context manager that marks ``repro.<name>`` in the trace."""
    return jax.profiler.TraceAnnotation("repro." + name, **args)


def enabled() -> bool:
    """Whether a profiler is recording spans now."""
    return jax.profiler.TraceAnnotation.is_enabled()


def nbytes(tree, host_only: bool = False) -> int:
    """Bytes of the arrays of ``tree``; with ``host_only``, of its NumPy
    arrays alone (what a call moves from the host to the device)."""
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree)
               if not host_only or isinstance(a, np.ndarray))
