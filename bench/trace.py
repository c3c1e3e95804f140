"""Reduction of a JAX profiler trace to the numbers the readers use.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  Device planes are those named
``/device:TPU:<n>``; on each, the line of XLA modules gives one event per
execution of a compiled program (``jit_<name>``) and the line of XLA ops
one event per operation.  The host plane carries the harness's own
annotations (``bench.<activity>``), which name what the host was doing
while the device sat idle.  Device time by the steps' ``jax.named_scope``s
(``scopes``) comes from the ops' framework names, which only xprof's
converter reads, at some tens of seconds a traced window.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench."
WINDOW = "bench.window"
# name-stack components JAX adds around user code
JAX_COMPONENTS = ("while", "body", "cond", "closed_call", "checkpoint",
                  "remat")

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


def op_scope(framework_name: str) -> Tuple[str, str]:
    """(program, top named scope) of an XLA op from its framework name:
    ``jit(edge_step)/while/body/closed_call/attention/kv_write/scatter:``
    -> ``("edge_step", "attention")``.  JAX's own components (``jit(...)``,
    loop and call bodies) are skipped, and the op itself is not a scope;
    an op outside every named scope gets ``"-"``."""
    parts = framework_name.split("/")
    head = parts[0]
    program = head[4:-1] if head.startswith("jit(") else head
    for p in parts[1:-1]:
        if "(" not in p and p not in JAX_COMPONENTS:
            return program, p
    return program, "-"


def scopes_from_hlo_stats(table: Dict) -> Dict[str, float]:
    """Device seconds by ``program:scope`` from xprof's ``hlo_stats``
    table (one row per XLA op).  Self time, so a loop or call adds only
    what its body's ops do not.  An op the compiler made has no framework
    name (a copy, a broadcast that fills a buffer): it counts under
    ``-`` of the program that its ``program_id`` names on another row, so
    that a program's scopes sum to its device time."""
    cols = [c["id"] for c in table["cols"]]
    name, self_us = cols.index("tf_op_name"), cols.index("total_self_time")
    pid = cols.index("program_id") if "program_id" in cols else None
    rows = [[c.get("v") for c in row["c"]] for row in table["rows"]]
    programs = {r[pid]: op_scope(r[name])[0] for r in rows
                if pid is not None and r[name]}
    out: Dict[str, float] = collections.defaultdict(float)
    for r in rows:
        if r[name]:
            program, scope = op_scope(r[name])
        elif pid is not None and r[pid] in programs:
            program, scope = programs[r[pid]], "-"
        else:
            continue
        out[f"{program}:{scope}"] += r[self_us] * 1e-6
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def device_scopes(xplane: str) -> Dict[str, float]:
    """``scopes_from_hlo_stats`` of a trace file, empty where the trace
    has no device ops.  The ops' framework names live in the trace's event
    metadata, which ``ProfileData`` does not expose; xprof's converter
    reads them, so this raises ImportError without the ``xprof``
    package."""
    from xprof.convert import raw_to_tool_data
    data, _ = raw_to_tool_data.xspace_to_tool_data([xplane], "hlo_stats", {})
    return scopes_from_hlo_stats(json.loads(data))


def scopes(xplane: str) -> Optional[Dict[str, float]]:
    """``device_scopes`` of a trace file, or None where they cannot be
    had: without ``xprof``, or where its converter fails on the trace
    (the reason goes to standard error)."""
    try:
        return device_scopes(xplane)
    except Exception as e:  # noqa: BLE001 - a missing or failing converter
        print(f"bench: device scopes unavailable: {e!r}", file=sys.stderr)
        return None


def reduce_dir(trace_dir: str) -> Optional[Dict]:
    """``reduce_profile`` of the newest trace under ``trace_dir``."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))
