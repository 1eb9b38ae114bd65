"""The program's profiler spans: every name in one place.

Two layers write into the JAX profiler's own trace, so that host and
device events share its clock:

* **Stages of the compiled round** (``jax.named_scope``). Each stage of
  the round step (``core/ltfl_step.py``) and of the scanned segment
  (``ScanRunner._segment``) runs under one scope name. A scope adds
  HLO metadata only: every operation XLA emits for the stage carries the
  name in its ``op_name`` path, the backward pass's operations inside
  ``transpose(jvp(...))``. ``op_paths`` reads those paths back from a
  compiled program's text and ``stage_of`` picks the innermost stage.
* **The engine's host work** (``span``, over
  ``jax.profiler.TraceAnnotation``). A span records only while a
  profiler session is active; otherwise it costs a no-op enter and exit.
  Counts ride on a span as its arguments.

Span arguments:

* ``seg``: on every host span, the number of segments the runner had
  absorbed when the span opened, so one segment's ``prepare``,
  ``dispatch`` and ``absorb`` spans share it;
* ``rounds`` and ``traces`` (segment traces so far) on ``dispatch``;
* ``uploads`` ((N,)-state uploads so far) on ``prepare``;
* ``fetches`` and ``fetch_bytes`` on ``absorb`` and ``sync``: the
  device-to-host reads made inside the span, all through one ``Reads``.
"""
from __future__ import annotations

import contextlib
import re
from typing import Dict, Iterator, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# stages of the compiled round (jax.named_scope)
PRUNE = "repro.prune"          # the pruning mask and its application
GRAD = "repro.grad"            # client forward and backward, mask multiply
RANGE = "repro.range"          # the range statistic and its registry update
COMPRESS = "repro.compress"    # the compressor (quantizer kernel), int8 path
AGGREGATE = "repro.aggregate"  # Eq. 19 and the server transform
UPDATE = "repro.update"        # the optimizer update and apply_updates
CHANNEL = "repro.channel"      # fading, transmissions, PER, delay/energy
SAMPLER = "repro.sampler"      # the cohort draw and the batch gather
CONTROL = "repro.control"      # Algorithm 1 in-scan, feedback, admission
EVAL = "repro.eval"            # the in-scan eval head
STAGES = (PRUNE, GRAD, RANGE, COMPRESS, AGGREGATE, UPDATE, CHANNEL,
          SAMPLER, CONTROL, EVAL)

# the engine's host work (span)
RUN = "repro.run"              # ScanRunner.run
PREPARE = "repro.prepare"      # segment constants and carry
DISPATCH = "repro.dispatch"    # the compiled segment's call
ABSORB = "repro.absorb"        # _absorb_segment, with the four below
FETCH = "repro.fetch"          # the segment's device-to-host reads
GAMMA = "repro.gamma"          # the float64 Eq. 29 loop
RECORDS = "repro.records"      # RoundRecords and post_round
CTL_ABSORB = "repro.ctl_absorb"  # the control program's absorb
SYNC = "repro.sync"            # _sync_host_population


class Reads:
    """The engine's device-to-host reads, counted: ``reads(x, dtype)`` is
    ``np.asarray(x, dtype)`` that adds one to ``count`` and the array's
    device bytes to ``bytes``."""

    def __init__(self):
        self.count = 0
        self.bytes = 0

    def __call__(self, x, dtype=None) -> np.ndarray:
        self.count += 1
        self.bytes += int(x.nbytes)
        return np.asarray(x, dtype)


@contextlib.contextmanager
def span(name: str, reads: Optional[Reads] = None,
         **counts: int) -> Iterator[TraceAnnotation]:
    """A host span named ``name`` carrying ``counts``; with ``reads``, also
    the reads made inside it (``fetches``, ``fetch_bytes``). Yields the
    annotation, whose ``set_metadata`` adds counts known only at the
    end."""
    with TraceAnnotation(name, **counts) as t:
        if reads is None:
            yield t
            return
        n, b = reads.count, reads.bytes
        try:
            yield t
        finally:
            t.set_metadata(fetches=reads.count - n,
                           fetch_bytes=reads.bytes - b)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*?"
                          r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)


def op_paths(hlo_text: str) -> Dict[str, str]:
    """Each instruction of an HLO module's text (``compiled.as_text()``)
    that carries metadata, mapped to its ``op_name`` path."""
    return dict(_INSTRUCTION.findall(hlo_text))


def stage_of(path: str) -> Optional[str]:
    """The innermost stage scope in an ``op_name`` path, or None."""
    best, at = None, -1
    for stage in STAGES:
        i = path.rfind(stage)
        if i > at:
            best, at = stage, i
    return best
