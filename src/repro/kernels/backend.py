"""Whether a Pallas kernel runs compiled or in the interpreter.

Compiled on a TPU; interpreted on the CPU, where the tests and the tiny
rehearsals run; refused on any other backend. There is no silent fallback
to the interpreter on an accelerator.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` as given, or (None) the choice for the default
    backend. Passing False on the CPU compiles for a described TPU, which
    is how the chip's compiler is rehearsed without the chip."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for a TPU and run interpreted on the "
        f"CPU; the default backend here is {backend!r}")
