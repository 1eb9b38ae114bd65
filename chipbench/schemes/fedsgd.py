"""FedSGD (McMahan et al. 2017), the paper's baseline: full-precision
gradients, no pruning, no quantization, and every device at half the
maximum power."""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

PRUNES = False
# the program logs a bit-width of 32 for an unquantized upload
_FULL_WIDTH = 32.0


def program(t: Dict):
    from repro.fed import FedSGDScheme
    return FedSGDScheme()


def compress(g: jax.Array, delta: jax.Array, key: jax.Array) -> jax.Array:
    return g


def payload(cfg: Dict, v: int, delta: np.ndarray) -> np.ndarray:
    """32 bits for each of the V weights."""
    return np.full(np.shape(delta), 32.0 * v)


def charged_power(cfg: Dict, recovered: np.ndarray) -> np.ndarray:
    """The power the round's delay and energy are charged at: FedSGD's
    fixed half of p_max (``decision_misses`` holds the packet error rates
    to it), which stays known where a deep fade leaves the rate at 1 and
    the power recovered from it undetermined."""
    return np.full(np.shape(recovered), 0.5 * cfg["wireless"]["p_max"])


def decision_misses(cfg: Dict, v: int, ch: Dict, rho: np.ndarray,
                    delta: np.ndarray, powers) -> np.ndarray:
    """Per device, whether the controls differ from FedSGD's fixed ones:
    no pruning, full width, half of p_max somewhere in the range of
    ``powers`` (see ``accounting.power_range``)."""
    half = 0.5 * cfg["wireless"]["p_max"]
    return (rho != 0) | (delta != _FULL_WIDTH) | (
        np.min(powers, axis=0) > half * (1 + 1e-4)) | (
        np.max(powers, axis=0) < half * (1 - 1e-4))
