"""One run of one cell: build, warm up, serve the window, check, report.

``run()`` is the library entry (the CLI in ``bench/run.py`` and the
tests call it).  Set-up is everything from process start to the window's
first due request: weights, the scheduler's pools, and a warm-up that
compiles every program the window runs.  The window then serves the
cell's traffic for ``seconds`` on the host clock; any compilation inside
it fails the run.  After the window the program's state is freed and a
sample of its answers is compared with the plain reference
(``bench.check``).
"""
from __future__ import annotations

import collections
import dataclasses
import json
import shutil
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench import check, spec, trace
from bench.driver import Loop, Served
from bench.traffic import Traffic
from bench.weights import dims

PEAKS = spec.BENCH / "peaks.json"
TRACE_DIR = spec.ROOT / ".bench_cache" / "trace"


def peaks_for(kind: str) -> Dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise spec.SpecError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return table[kind]


def device_info() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cfg: Dict
    cell: Dict
    mix: Dict
    seconds: float
    setup_s: float
    t0: float
    t_end: float
    records: List
    ticks: List
    trace: Optional[Dict] = None
    peaks: Optional[Dict] = None

    @property
    def exits(self):
        return tuple(self.cfg["assumed"]["exit_layers"])

    @property
    def layers(self) -> int:
        return dims(self.cfg)["layers"]

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t_end


class _Compiles:
    """Counts JAX's compilations while armed.  JAX keeps its listeners for
    the life of the process, so there is one counter per process."""
    _one: Optional["_Compiles"] = None

    def __init__(self):
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def counter(cls) -> "_Compiles":
        if cls._one is None:
            cls._one = cls()
        cls._one.armed, cls._one.n = False, 0
        return cls._one

    def _on(self, name: str, *_a, **_kw) -> None:
        if self.armed and name.endswith("backend_compile_duration"):
            self.n += 1


def _malformed(records, vocab: int) -> int:
    return sum(1 for r in records if r.done and (
        len(r.tokens) != r.req.max_new
        or any(not 0 <= t < vocab for t in r.tokens)))


def theta_for_share(records, share: float) -> Optional[float]:
    """The theta at which ``share`` of the window's decoded tokens would
    go to the cloud (both exit confidences below theta), from the
    confidences the program reported for them."""
    conf = np.asarray([c for r in records if r.done for c in r.confidences])
    if not len(conf):
        return None
    lo, hi = 0.0, 1.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean((conf[:, 0] < mid) & (conf[:, 1] < mid)) < share:
            lo = mid
        else:
            hi = mid
    return hi


def run(c: Dict, *, seed: int, seconds: float, trace_on: bool,
        t_start: float, control: bool = False, rate: Optional[float] = None,
        peaks: Optional[Dict] = None,
        log: Callable[[str], None] = lambda m: None,
        clock: Callable[[], float] = time.perf_counter) -> Dict:
    """Run cell ``c`` (from ``spec.cell``) once; return the result line's
    object plus an ``info`` dict of what else was observed.  ``clock``
    drives the window (arrivals, stamps, its length); set-up is timed on
    the host clock."""
    cfg, cell, mix = c["config"], c["cell"], c["traffic"]
    compiles = _Compiles.counter()
    t_built = time.perf_counter()
    log(f"building {c['name']} at {t_built - t_start:.1f}s")
    served = Served(cfg, cell, seed)
    loop = Loop(served, clock=clock, annotate=trace_on)
    t_warm = time.perf_counter()
    log(f"weights and pools ready at {t_warm - t_start:.1f}s")
    rounds = loop.warm_up(mix, dims(cfg)["vocab"],
                          log=lambda m: log(f"{m} at "
                                            f"{time.perf_counter() - t_start:.1f}s"))
    traffic = Traffic(mix, seed=seed, vocab=dims(cfg)["vocab"],
                      slots=int(cell["slots"]), rate=rate)
    traces0 = served.traces()
    if trace_on:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    compiles.armed = True
    t_open = time.perf_counter()
    win = loop.run(traffic, seconds)
    compiles.armed = False
    log(f"window closed at {time.perf_counter() - t_start:.1f}s")
    if trace_on:
        jax.profiler.stop_trace()
    in_window = served.traces() - traces0 + compiles.n
    stats = jax.devices()[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices()) if stats else 0
    records = [r for r in loop.records.values() if r.req.index >= 0]
    ticks = [t for t in loop.ticks if t.start >= win["t0"]]
    loop.sched = None
    served.close()
    del loop, served
    readers = {name: spec.reader(name)
               for name in (c["per_layer"] if trace_on else c["end_to_end"])}
    summary = None
    if trace_on:
        summary = trace.reduce_dir(str(TRACE_DIR))
        if summary is not None and any(getattr(m, "SCOPES", False)
                                       for m in readers.values()):
            summary["scopes"] = trace.scopes(
                trace.find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    t_ref = time.perf_counter()
    free = jax.devices()[0].memory_stats() or {}
    log(f"program state freed: {free.get('bytes_in_use')} bytes in use")
    chk = check.run(records, cfg, cell, mix, seed, control=control, log=log)
    ref_s = time.perf_counter() - t_ref
    log(f"reference compared in {ref_s:.1f}s")
    r = Run(cfg=cfg, cell=cell, mix=mix, seconds=seconds,
            setup_s=t_open - t_start, t0=win["t0"], t_end=win["t_end"],
            records=records, ticks=ticks, trace=summary, peaks=peaks)
    metrics = {}
    for name, reader in readers.items():
        value = reader.read(r)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": c["units"][name]}
    due = [x for x in records if x.due < win["t_end"]]
    failed = _malformed(records, dims(cfg)["vocab"])
    errors = list(chk["errors"])
    if in_window:
        errors.append(f"{in_window} compilations inside the window")
    if failed:
        errors.append(f"{failed} finished requests malformed")
    limits = cell["check"]["limits"]
    correct, verdict = check.verdict(chk["numbers"], errors, limits)
    dev = device_info()
    dev["memory_peak_bytes"] = peak
    if trace_on and summary is not None:
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
    out = {"correct": correct, "attempted": len(due),
           "failed": failed, "metrics": metrics, "device": dev}
    if trace_on and summary is not None:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["check"] = verdict
    late = np.asarray(win["late_s"] or [0.0]) * 1e3
    info = {"errors": errors, "warmup_rounds": rounds,
            "setup_phases_s": {"start": t_built - t_start,
                               "weights_and_pools": t_warm - t_built,
                               "warm_up": t_open - t_warm},
            "generator_late_ms": {"p50": float(np.percentile(late, 50)),
                                  "p99": float(np.percentile(late, 99)),
                                  "max": float(late.max())},
            "backlog_at_end": win["backlog"], "reference_s": ref_s,
            "checked_tokens": chk["tokens"],
            "check_worst": chk.get("worst", {}),
            "finished": sum(1 for x in records if x.done),
            "calls": dict(collections.Counter(t.kind for t in ticks)),
            "served_by": dict(collections.Counter(
                s for x in records if x.done
                for s in check.sources_of(x)))}
    if "control" in chk:
        ok, table = check.verdict(chk["control"], [], limits)
        info["control"] = {"correct": ok, "check": table}
    target = cell.get("calibrate", {}).get("cloud_share")
    if target is not None:
        info["theta_for_cloud_share"] = theta_for_share(records, target)
    if trace_on:
        info["trace_programs"] = (summary or {}).get("programs")
    return {"result": out, "info": info}
