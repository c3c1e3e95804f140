"""Reduction of a JAX profiler trace to the numbers the readers use.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  Device planes are those named
``/device:TPU:<n>``; on each, the line of XLA modules gives one event per
execution of a compiled program (``jit_<name>``) and the line of XLA ops
one event per operation.  The host plane carries the harness's own
annotations (``bench.<activity>``), which name what the host was doing
while the device sat idle.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench."
WINDOW = "bench.window"

Interval = Tuple[float, float]


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def program_name(event_name: str) -> str:
    """``jit_edge_step(123)`` -> ``edge_step``."""
    name = re.sub(r"\(\d+\)$", "", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce_profile(pd) -> Optional[Dict]:
    """Summary of one ``ProfileData``, or None where it holds no window or
    no device activity inside it."""
    window: Optional[Interval] = None
    host: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(HOST_PREFIX):
                    continue
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW:
                    window = (s, e)
                else:
                    host.append((s, e, ev.name[len(HOST_PREFIX):]))
    if window is None or not devices:
        return None
    lo, hi = window
    programs: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0, 0.0])
    ops: Dict[str, float] = collections.defaultdict(float)
    busy_total, gaps_all = 0.0, []
    for plane in devices:
        op_ev, mod_ev = [], []
        for line in plane.lines:
            is_mod = "Module" in line.name
            if not (is_mod or line.name == "XLA Ops"):
                continue
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if e > lo and s < hi:
                    (mod_ev if is_mod else op_ev).append((s, e, ev.name))
        mod_ev.sort()
        for s, e, name in mod_ev:
            p = programs[program_name(name)]
            p[0] += 1
            p[1] += (e - s) * 1e-9
        mod_starts = [m[0] for m in mod_ev]
        for s, e, name in op_ev:
            i = bisect.bisect_right(mod_starts, s) - 1
            prog = (program_name(mod_ev[i][2])
                    if i >= 0 and mod_ev[i][1] >= e else "?")
            ops[f"{prog}:{op_name(name)}"] += (e - s) * 1e-9
        busy = _union(_clip([(s, e) for s, e, _ in (op_ev or mod_ev)],
                            lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps_all += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    idle: Dict[str, float] = collections.defaultdict(float)
    host.sort()
    starts = [h[0] for h in host]
    longest = max((e - s for s, e, _ in host), default=0.0)
    for gs, ge in gaps_all:
        best, what = 0.0, "none"
        i = bisect.bisect_left(starts, gs - longest)
        while i < len(host) and host[i][0] < ge:
            hs, he, name = host[i]
            ov = min(ge, he) - max(gs, hs)
            if ov > best:
                best, what = ov, name
            i += 1
        idle[what] += (ge - gs) * 1e-9 / len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / len(devices),
        "devices": len(devices),
        "programs": {k: {"count": int(v[0]), "seconds": v[1]}
                     for k, v in programs.items()},
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:10],
    }


def reduce_dir(trace_dir: str) -> Optional[Dict]:
    """``reduce_profile`` of the newest trace under ``trace_dir``."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))
