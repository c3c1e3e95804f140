"""Named phases of the serving engine's host work.

``Phases.phase(name, **args)`` opens a profiler span named
``engine.<name>`` (``jax.profiler.TraceAnnotation``: it lands in a running
profiler trace on the same clock as the device's events, with ``args`` as
the event's stats) and, with or without a profiler running, adds one call
and the phase's host seconds to the counters.  Phases nest; a phase's
seconds include those of the phases opened inside it.

    ph = Phases()
    with ph.phase("tick", rows=8):
        with ph.phase("edge_fetch", rows=8):
            ...
    ph.snapshot()   # {"engine.tick": {"calls": 1, "seconds": ...}, ...}

docs/serving.md §Tracing lists the engine's phases.
"""
from __future__ import annotations

import time
from typing import Dict

from jax.profiler import TraceAnnotation

PREFIX = "engine."


class _Phase:
    __slots__ = ("_owner", "_name", "_span", "_t0")

    def __init__(self, owner: "Phases", name: str, args: Dict):
        self._owner = owner
        self._name = name
        self._span = TraceAnnotation(name, **args)

    def __enter__(self) -> None:
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        o, n = self._owner, self._name
        o.calls[n] = o.calls.get(n, 0) + 1
        o.seconds[n] = o.seconds.get(n, 0.0) + dt
        return False


class Phases:
    """Call counts and host seconds per phase, since the last ``reset``."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.calls: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}

    def phase(self, name: str, **args) -> _Phase:
        return _Phase(self, PREFIX + name, args)

    def snapshot(self) -> Dict[str, Dict]:
        return {n: {"calls": self.calls[n], "seconds": self.seconds[n]}
                for n in sorted(self.calls)}


def total(snapshots) -> Dict[str, Dict]:
    """The sum of several ``snapshot``s (e.g. one per engine of a run)."""
    out: Dict[str, Dict] = {}
    for snap in snapshots:
        for n, v in snap.items():
            t = out.setdefault(n, {"calls": 0, "seconds": 0.0})
            t["calls"] += v["calls"]
            t["seconds"] += v["seconds"]
    return out
