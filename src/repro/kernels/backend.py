"""Where a Pallas kernel runs: natively, or under the interpreter on CPU."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True exactly when the default backend is
    the CPU (which cannot lower Pallas TPU kernels)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
