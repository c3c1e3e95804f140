"""The serving engine's own phases in a profiler trace.

The engine opens a host span ``engine.<phase>`` around each phase of an
admission or a tick (``repro.serving.phases``; docs/serving.md lists
them).  ``reduce_phases`` reads a ``jax.profiler.ProfileData`` the way
``bench.trace.reduce_profile`` does (the same ``bench.window``, the same
device-idle gaps) and puts each gap down to the program: every gap is cut
at the engine spans' edges and each piece is credited to the innermost
span over it, or to ``outside`` where no engine span is open (the
harness's own code between calls, and its waits for arrivals).  The
pieces partition the window's idle time, so the phases' ``idle_seconds``
sum to it.  ``device_scopes`` (from ``bench.trace``) puts the device's
time down to the steps' ``jax.named_scope``s (``attention``, ``mlp``,
``exit_heads``, ``output_head``).

Run as a script, it serves one cell's window as ``bench/run.py`` does
(the same build, warm-up and traffic; no reference check) and prints one
JSON line: the device, the compilations inside the window, the window's
``phases`` counters from ``BatchScheduler.phases`` and, with
``--trace 1``, the reduction of its trace beside the harness's
``idle_gaps``:

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Like ``bench/run.py``, it prints nothing and exits 2 without a TPU, or
with fewer chips than the cell asks for.  ``device_scopes`` needs the
``xprof`` package; without it the line says so in that key's place.
The trace stays in ``.bench_cache/phase_trace`` for a trace viewer.
"""
from __future__ import annotations

import bisect
import collections
import json
import pathlib
import shutil
import sys
import time
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import spec, trace  # noqa: E402
# the device-scope reduction lives in bench.trace; re-exported
from bench.trace import (JAX_COMPONENTS, device_scopes, op_scope,  # noqa: E402,F401
                         scopes_from_hlo_stats)

ENGINE = "engine."
TRACE_DIR = spec.ROOT / ".bench_cache" / "phase_trace"
OUTSIDE = "outside"
# a tick or admission longer than this is reported under ``stalls``
STALL_S = 0.15

Span = Tuple[float, float, str]


def span_name(event_name: str) -> str:
    """``engine.tick#rows=8#`` -> ``engine.tick`` (a trace event's name may
    carry its annotation's arguments)."""
    return event_name.split("#", 1)[0]


def innermost(spans: List[Span], lo: float, hi: float) -> List[Span]:
    """Pieces that partition ``[lo, hi]``, each naming the innermost span
    open over it, or ``OUTSIDE``.  Spans nest (one thread opens them)."""
    pieces: List[Span] = []
    open_: List[Tuple[float, str]] = []          # (end, name), innermost last
    t = lo

    def upto(x: float) -> None:
        nonlocal t
        if x > t:
            pieces.append((t, x, open_[-1][1] if open_ else OUTSIDE))
            t = x

    def close(before: float) -> None:
        while open_ and open_[-1][0] <= before:
            upto(open_[-1][0])
            open_.pop()

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        close(s)
        upto(s)
        open_.append((e, name))
    close(hi)
    upto(hi)
    return pieces


def _overlaps(a: List[Tuple[float, float]], pieces: List[Span]):
    """(piece name, length) of every overlap of the sorted, disjoint
    intervals ``a`` with the sorted, disjoint ``pieces``."""
    i = j = 0
    while i < len(a) and j < len(pieces):
        s = max(a[i][0], pieces[j][0])
        e = min(a[i][1], pieces[j][1])
        if e > s:
            yield pieces[j][2], e - s
        if a[i][1] < pieces[j][1]:
            i += 1
        else:
            j += 1


def reduce_phases(pd) -> Optional[Dict]:
    """``phases``: for each engine span name, and ``outside``, its
    ``calls``, host ``seconds``, ``self_seconds`` (less the spans nested
    in it) and ``idle_seconds`` (device idle credited to it), in the
    window.  ``idle_s`` is the window's device idle (per device);
    ``stalls`` each tick or admission longer than ``STALL_S``, with its
    seconds by innermost span.  None without a window or a device."""
    window: Optional[Tuple[float, float]] = None
    spans: List[Span] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if ev.name == trace.WINDOW:
                    window = (s, e)
                elif ev.name.startswith(ENGINE):
                    spans.append((s, e, span_name(ev.name)))
    if window is None or not devices:
        return None
    lo, hi = window
    spans = [(max(s, lo), min(e, hi), n) for s, e, n in spans
             if e > lo and s < hi]
    pieces = innermost(spans, lo, hi)
    out: Dict[str, Dict] = {}
    for s, e, name in spans:
        p = out.setdefault(name, {"calls": 0, "seconds": 0.0,
                                  "self_seconds": 0.0, "idle_seconds": 0.0})
        p["calls"] += 1
        p["seconds"] += (e - s) * 1e-9
    out[OUTSIDE] = {"calls": 0, "seconds": 0.0, "self_seconds": 0.0,
                    "idle_seconds": 0.0}
    for s, e, name in pieces:
        out[name]["self_seconds"] += (e - s) * 1e-9
    out[OUTSIDE]["seconds"] = out[OUTSIDE]["self_seconds"]
    idle_total = 0.0
    for plane in devices:
        op_ev, mod_ev = [], []
        for line in plane.lines:
            is_mod = "Module" in line.name
            if not (is_mod or line.name == "XLA Ops"):
                continue
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if e > lo and s < hi:
                    (mod_ev if is_mod else op_ev).append((s, e))
        busy = trace._union(trace._clip(op_ev or mod_ev, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, length in _overlaps(gaps, pieces):
            out[name]["idle_seconds"] += length * 1e-9 / len(devices)
            idle_total += length * 1e-9 / len(devices)
    result = {"window_s": (hi - lo) * 1e-9, "idle_s": idle_total,
              "phases": out}
    stalls = [_split(s, e, name, pieces) for s, e, name in spans
              if name in (ENGINE + "tick", ENGINE + "admit")
              and (e - s) * 1e-9 > STALL_S]
    if stalls:
        result["stalls"] = stalls
    return result


def _split(s: float, e: float, name: str, pieces: List[Span]) -> Dict:
    """Span ``name`` over ``[s, e]``: its seconds and theirs by innermost
    span."""
    by: Dict[str, float] = collections.defaultdict(float)
    i = max(0, bisect.bisect_right([p[0] for p in pieces], s) - 1)
    while i < len(pieces) and pieces[i][0] < e:
        ov = min(e, pieces[i][1]) - max(s, pieces[i][0])
        if ov > 0:
            by[pieces[i][2]] += ov * 1e-9
        i += 1
    return {"span": name, "seconds": (e - s) * 1e-9, "by_span": dict(by)}


def idle_by_phase(reduced: Dict) -> Dict[str, float]:
    """Device-idle seconds per phase (``outside`` included), largest
    first."""
    return dict(sorted(((n, p["idle_seconds"])
                        for n, p in reduced["phases"].items()),
                       key=lambda kv: -kv[1]))


def delta(after: Dict[str, Dict], before: Dict[str, Dict]) -> Dict:
    """The phases counted between two ``Phases.snapshot``s."""
    out = {}
    for n, a in after.items():
        b = before.get(n, {"calls": 0, "seconds": 0.0})
        if a["calls"] > b["calls"]:
            out[n] = {"calls": a["calls"] - b["calls"],
                      "seconds": a["seconds"] - b["seconds"]}
    return out


def window(c: Dict, *, seed: int, seconds: float, trace_on: bool,
           clock=time.perf_counter) -> Dict:
    """Build cell ``c`` (from ``spec.cell``), warm it up and serve one
    window of its traffic, as ``bench.harness.run`` does.  Returns the
    window's tick count, compilations inside it (``compiles``; the harness
    fails a run with any) and phase counters (``phases``) and, traced, the
    reduction of its trace (``trace``, ``idle_by_phase``,
    ``device_scopes``) with the harness's own view of it (``busy_s``,
    ``idle_gaps``, ``programs``).  ``device_scopes`` is a string saying
    why where it cannot be read."""
    import jax

    from bench.driver import Loop, Served
    from bench.harness import _Compiles
    from bench.traffic import Traffic
    from bench.weights import dims

    cfg, cell, mix = c["config"], c["cell"], c["traffic"]
    compiles = _Compiles.counter()
    served = Served(cfg, cell, seed)
    loop = Loop(served, clock=clock, annotate=trace_on)
    loop.warm_up(mix, dims(cfg)["vocab"])
    traffic = Traffic(mix, seed=seed, vocab=dims(cfg)["vocab"],
                      slots=int(cell["slots"]))
    before = served.sched.phases.snapshot()
    traces0 = served.traces()
    if trace_on:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    compiles.armed = True
    win = loop.run(traffic, seconds)
    compiles.armed = False
    if trace_on:
        jax.profiler.stop_trace()
    out = {"ticks": sum(1 for t in loop.ticks
                        if t.kind == "tick" and t.start >= win["t0"]),
           "compiles": served.traces() - traces0 + compiles.n,
           "phases": delta(served.sched.phases.snapshot(), before)}
    loop.sched = None
    served.close()
    if trace_on:
        xplane = trace.find_xplane(str(TRACE_DIR))
        pd = jax.profiler.ProfileData.from_file(xplane)
        reduced = reduce_phases(pd)
        if reduced is not None:
            out["trace"] = reduced
            out["idle_by_phase"] = idle_by_phase(reduced)
            try:
                out["device_scopes"] = (device_scopes(xplane)
                                        or "unavailable (no device ops)")
            except ImportError:
                out["device_scopes"] = "unavailable (no xprof)"
        view = trace.reduce_profile(pd) or {}
        for k in ("busy_s", "idle_gaps", "programs"):
            out[k] = view.get(k)
    return out


def main(argv=None) -> int:
    import argparse

    import jax

    from bench import harness
    from bench.run import CACHE

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = spec.cell(args.workload)
    except spec.SpecError as e:
        print(f"phases: {e}", file=sys.stderr)
        return 2
    info = harness.device_info()
    chips = int(c["entry"]["chips"])
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"phases: needs {chips} TPU chip(s); JAX found "
              f"{info['count']} {info['platform']} device(s); nothing was "
              f"run", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = window(c, seed=args.seed, seconds=args.seconds,
                 trace_on=bool(args.trace))
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": info, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
