"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) holds a
plane per device (``/device:TPU:<n>``) whose ``XLA Ops`` line has one
event per operation run on the chip and whose ``XLA Modules`` line has
one event per execution of a compiled program, and a host plane whose
threads carry the harness's ``TraceAnnotation`` spans. Times are on one
clock, in nanoseconds.

The window is the span from the start of the first ``chipbench.call``
annotation to the end of the last. Every interval is clipped to it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

CALL = "chipbench.call"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns
    label: str = ""     # the name and the event's string stats (op path)

    def matches(self, *parts: str) -> bool:
        text = self.label or self.name
        return all(p in text for p in parts)

    @property
    def op(self) -> str:
        """The operation's own name: an HLO op event's name is its whole
        instruction, whose operand list names other operations."""
        return self.name.split(" = ", 1)[0]


def _label(e) -> str:
    return " ".join([e.name] + [v for _, v in e.stats if isinstance(v, str)])


@dataclass
class Trace:
    window: Interval
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # per device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # per device
    host: List[Event] = field(default_factory=list)                # spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def devices(self) -> List[str]:
        return sorted(self.ops)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read a trace file into a ``Trace``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                             _label(e)) for e in line.events]
                (ops if line.name == OPS_LINE else modules).setdefault(
                    plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("chipbench."))
    calls = [e for e in host if e.name == CALL]
    if not calls:
        raise ValueError(f"the trace has no {CALL!r} span")
    window = (min(e.start for e in calls), max(e.end for e in calls))
    return Trace(window=window, ops=ops, modules=modules,
                 host=sorted(host, key=lambda e: e.start))


def dump(trace: Trace, path: str) -> None:
    """Write the trace's events inside its window to a gzipped JSON file,
    with each distinct label stored once."""
    labels: Dict[str, int] = {}

    def rows(events):
        return [[e.name, e.start, e.end,
                 labels.setdefault(e.label, len(labels))]
                for e in events if e.end > trace.window[0]
                and e.start < trace.window[1]]

    doc = {"window": list(trace.window),
           "ops": {d: rows(v) for d, v in trace.ops.items()},
           "modules": {d: rows(v) for d, v in trace.modules.items()},
           "host": rows(trace.host)}
    doc["labels"] = sorted(labels, key=labels.get)
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def restore(path: str) -> Trace:
    """Read a file that ``dump`` wrote."""
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    labels = doc["labels"]

    def events(rows):
        return [Event(n, a, b, labels[i]) for n, a, b, i in rows]

    return Trace(window=tuple(doc["window"]),
                 ops={d: events(v) for d, v in doc["ops"].items()},
                 modules={d: events(v) for d, v in doc["modules"].items()},
                 host=events(doc["host"]))


def clip(events: Sequence[Event], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_s(trace: Trace, device: str) -> float:
    """Seconds of the window in which some operation ran on ``device``."""
    return sum(b - a for a, b in union(clip(trace.ops.get(device, []),
                                            trace.window))) * 1e-9


def idle_gaps(trace: Trace, device: str) -> List[Interval]:
    """The stretches of the window with no operation on ``device``."""
    busy = union(clip(trace.ops.get(device, []), trace.window))
    gaps, t = [], trace.window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < trace.window[1]:
        gaps.append((t, trace.window[1]))
    return gaps


def between_executions(trace: Trace, device: str, *match: str) -> float:
    """Seconds from the end of each execution of a program whose name
    holds every string of ``match`` to the start of the next one, within
    the window."""
    runs = sorted(clip([e for e in trace.modules.get(device, [])
                        if e.matches(*match)], trace.window))
    return sum(max(0.0, nxt[0] - cur[1])
               for cur, nxt in zip(runs, runs[1:])) * 1e-9


def op_seconds(trace: Trace, device: str, *match: str,
               any_of: Sequence[str] = (), own: Sequence[str] = ()) -> float:
    """Summed device time of the operations whose name or op path holds
    every string of ``match``, where ``any_of`` is given one of its
    strings, and whose own name (``Event.op``) holds every string of
    ``own``."""
    return sum(b - a for a, b in clip(
        [e for e in trace.ops.get(device, []) if e.matches(*match) and (
            not any_of or any(e.matches(x) for x in any_of))
         and all(x in e.op for x in own)],
        trace.window)) * 1e-9


def host_span_at(trace: Trace, t: float) -> str:
    """The innermost harness span open at time ``t`` ("harness" when
    none is)."""
    best: Optional[Event] = None
    for e in trace.host:
        if e.start <= t < e.end and (best is None or e.start >= best.start):
            best = e
    return best.name if best is not None else "harness"


# control flow whose events span the operations they run
CONTAINERS = ("%while", "%conditional", "%call")


def breakdown(trace: Trace, device: str, top: int = 10) -> Dict[str, list]:
    """The operations that took most device time, by their own names and
    without the loops and calls that hold them, and the longest idle gaps
    named by what the harness was doing as each began."""
    per_op: Dict[str, float] = {}
    for e in trace.ops.get(device, []):
        a, b = max(e.start, trace.window[0]), min(e.end, trace.window[1])
        if b > a and not e.op.startswith(CONTAINERS):
            per_op[e.op] = per_op.get(e.op, 0.0) + (b - a) * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, device), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[host_span_at(trace, a), (b - a) * 1e-9]
                          for a, b in gaps]}
