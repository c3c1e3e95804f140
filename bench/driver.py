"""The system under test, and the wall-clock loop that drives it.

The program serves through ``BatchScheduler`` (continuous batching over a
pool of slots).  It has no public wall-clock API, so this module holds its
own copy of the loop in ``BatchScheduler.run``: a request enters the
admission queue only once its due time has passed on the host clock, and
after each admission and each ``tick()`` the loop stamps, on the host
clock, every token each slot committed.  The scheduler's private names
used here are ``_admit`` and ``_collect`` (plus the public ``slots`` and
the slots' ``req``/``active``/``tokens``/``events``/``stats`` fields).

Weights come from ``bench.weights`` (not from the program's own init) and
are laid out into the program's parameter tree in one jitted call, in the
served dtype; the architecture's adapter (``bench.arch``) names the
program's leaves and builds its ``ModelConfig``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch
from bench import weights as W
from bench.traffic import Req, Traffic, warmup_lengths

# warm-up serves at least this many rounds (so that every step kind, the
# cloud step among them, has run with near certainty) and stops at the
# first later round that traces nothing new
WARMUP_ROUNDS, WARMUP_MAX_ROUNDS = 3, 8


def _path(keys) -> tuple:
    out = []
    for k in keys:
        out.append(getattr(k, "key", getattr(k, "idx", k)))
    return tuple(out)


def build_params(model, cfg: Dict, key: jax.Array):
    """The program's parameter tree holding ``bench.weights`` tensors, made
    on the device in one jitted call of the key (so every seed runs the
    same compiled program)."""
    specs = model.param_specs()
    segs = model.segments
    dtype = model.param_dtype
    leaves = arch.of(cfg).LEAVES

    def make(key, path, spec):
        p = _path(path)
        if p in (("embed",), ("lm_head",), ("final_norm",)):
            x = W.tensor(cfg, key, p[0], 0, dtype)
        elif p[0] == "exit_norms":
            x = W.tensor(cfg, key, "exit_norm", int(p[1]), dtype)
        elif p[0] == "segments" and p[2:] in leaves:
            seg = segs[p[1]]
            name = leaves[p[2:]]
            x = jax.vmap(lambda l: W.tensor(cfg, key, name, l, dtype))(
                jnp.arange(seg.start, seg.end))
        else:
            raise ValueError(f"the benchmark has no weights for program "
                             f"parameter {p}")
        if x.shape != spec.shape:
            raise ValueError(f"parameter {p}: program shape {spec.shape}, "
                             f"configuration shape {x.shape}")
        # drawn in the served dtype; a leaf the program keeps wider (a
        # router in float32) holds the same values
        return x.astype(spec.dtype)

    return jax.jit(lambda k: jax.tree_util.tree_map_with_path(
        lambda path, spec: make(k, path, spec), specs))(key)


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return arch.of(cfg).model_config(cfg)


class Served:
    """One cell's model, weights and scheduler."""

    def __init__(self, cfg: Dict, cell: Dict, seed: int):
        from repro.core.collm import CoLLM, CollmConfig
        from repro.core.content_manager import ContentManager
        from repro.core.transport import SyncChannel
        from repro.models.transformer import Model
        from repro.serving.engine import BatchScheduler
        from repro.serving.mesh_exec import mesh_context

        self.cfg, self.cell = cfg, cell
        self.model = Model(model_config(cfg),
                           param_dtype=W.served_dtype(cfg))
        ccfg = CollmConfig(theta=float(cell["theta"]),
                           wire_format=cfg["assumed"]["wire_format"],
                           kv_layout=cell["kv_layout"])
        self.collm = CoLLM(self.model, ccfg)
        params = jax.block_until_ready(
            build_params(self.model, cfg, W.base_key(seed)))
        self.sched = BatchScheduler(
            self.collm, params, ContentManager(), int(cell["slots"]),
            int(cell["max_seq"]), mode=cell["mode"], sampler="greedy",
            channel=SyncChannel())
        del params
        self.mesh = mesh_context(self.collm)

    def traces(self) -> int:
        return sum(self.mesh.trace_counts.values())

    def close(self) -> None:
        """Drop every device buffer the program holds."""
        self.sched = None
        self.collm = None
        gc.collect()


@dataclasses.dataclass
class Record:
    """One request's life on the host clock (seconds of perf_counter)."""
    req: Req
    due: float
    queued: float = 0.0
    admitted: Optional[float] = None
    slot: int = -1
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    sources: List[str] = dataclasses.field(default_factory=list)
    cloud_requests: int = 0
    confidences: List[tuple] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class Tick:
    """One engine call of the window: an admission or a tick."""
    kind: str                  # "admit" | "tick"
    start: float
    end: float
    active: int = 0            # slots holding a request after the call
    edge_ctx: List[int] = dataclasses.field(default_factory=list)
    cloud_ctx: List[int] = dataclasses.field(default_factory=list)
    prompts: List[int] = dataclasses.field(default_factory=list)


class Loop:
    """Drives ``sched`` from the host clock and records what it served."""

    def __init__(self, served: Served, clock: Callable[[], float] =
                 time.perf_counter, annotate: bool = False):
        self.s = served
        self.sched = served.sched
        self.clock = clock
        self.annotate = annotate
        self.records: Dict[int, Record] = {}
        self.ticks: List[Tick] = []
        self._slot: List[Optional[Record]] = [None] * self.sched.B
        self._seen = [0] * self.sched.B
        self._queue: "collections.deque" = collections.deque()

    def _span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()

    def _enqueue(self, rec: Record, now: float) -> None:
        from repro.serving.engine import Request
        rec.queued = now
        self.records[rec.req.index] = rec
        self._queue.append(Request(device_id=f"r{rec.req.index}",
                                   prompt=rec.req.prompt,
                                   max_new=rec.req.max_new,
                                   index=rec.req.index))

    def _stamp(self, now: float, tick: Tick) -> List[Record]:
        """Stamp new tokens; return the records that finished."""
        done = []
        for s in self.sched.slots:
            rec = self._slot[s.index]
            if rec is None and s.req is not None:
                rec = self.records[s.req.index]
                self._slot[s.index] = rec
                self._seen[s.index] = 0
                rec.admitted = now
                rec.slot = s.index
                rec.cloud_requests = s.stats.cloud_requests
                tick.prompts.append(len(rec.req.prompt))
            if rec is None:
                continue
            new = len(s.tokens) - self._seen[s.index]
            if new > 0:
                p = len(rec.req.prompt)
                for i in range(self._seen[s.index], len(s.tokens)):
                    ev = s.events[i]
                    if i > 0 and ev != "admit":
                        tick.edge_ctx.append(p + i)
                        if ev == "cloud":
                            tick.cloud_ctx.append(
                                p + sum(1 for e in s.events[1:i + 1]
                                        if e == "cloud"))
                rec.stamps.extend([now] * new)
                self._seen[s.index] = len(s.tokens)
            if not s.active:
                rec.tokens = [int(t) for t in s.tokens]
                rec.sources = list(s.events)
                rec.confidences = list(s.stats.confidences)
                rec.done = True
                done.append(rec)
                self._slot[s.index] = None
        tick.active = sum(1 for s in self.sched.slots if s.active)
        return done

    def _call(self, kind: str, fn) -> List[Record]:
        t = Tick(kind=kind, start=self.clock(), end=0.0)
        with self._span(kind):
            fn()
        t.end = self.clock()
        done = self._stamp(t.end, t)
        self.sched._collect({}, {})
        self.ticks.append(t)
        return done

    def run(self, traffic: Traffic, seconds: float,
            t0: Optional[float] = None) -> Dict:
        """Serve ``traffic`` for ``seconds`` from ``t0`` (now by default).
        Returns the window's bounds and the generator's lateness."""
        t0 = self.clock() if t0 is None else t0
        t_end = t0 + seconds
        closed = traffic.loop == "closed"
        first = traffic.first_closed() if closed else \
            traffic.open_loop(seconds)
        pending = collections.deque(
            sorted((Record(req=r, due=t0 + r.due) for r in first),
                   key=lambda rec: rec.due))
        late = []
        with self._span("window"):
            while True:
                now = self.clock()
                if now >= t_end:
                    break
                while pending and pending[0].due <= now:
                    rec = pending.popleft()
                    late.append(now - rec.due)
                    self._enqueue(rec, now)
                done = []
                if self._queue and any(s.req is None
                                       for s in self.sched.slots):
                    done += self._call(
                        "admit", lambda: self.sched._admit(self._queue))
                if any(s.active for s in self.sched.slots):
                    done += self._call("tick", self.sched.tick)
                if closed:
                    for rec in done:
                        nxt = traffic.next_for(rec.req.client, 0.0)
                        pending.append(Record(req=nxt, due=self.clock()))
                if not self._queue and not any(s.active
                                               for s in self.sched.slots):
                    wake = min(pending[0].due if pending else t_end, t_end)
                    with self._span("wait"):
                        while self.clock() < wake:
                            time.sleep(min(1e-3, max(0.0,
                                                     wake - self.clock())))
        backlog = len(self._queue) + sum(1 for p in pending if p.due < t_end)
        return {"t0": t0, "t_end": t_end, "late_s": late, "backlog": backlog}

    def warm_up(self, traffic_mix: Dict, vocab: int,
                log=lambda m: None) -> int:
        """Compile every program the window will run: serve rounds of
        requests at each prompt-length bucket boundary of the mix (and
        enough decode ticks that every step kind occurs) until a round adds
        no new trace.  Returns the rounds served."""
        rng = np.random.default_rng(0)
        lengths = warmup_lengths(traffic_mix["prompt_len"])
        b = self.sched.B
        for r in range(WARMUP_MAX_ROUNDS):
            before = self.s.traces()
            reqs = [Req(index=-(r * 10_000 + i + 1),
                        prompt=rng.integers(0, vocab, n).astype(np.int32),
                        max_new=4)
                    for i, n in enumerate(lengths + [lengths[-1]] *
                                          max(0, b - len(lengths)))]
            for q in reqs:
                self._enqueue(Record(req=q, due=0.0), self.clock())
            while self._queue or any(s.active for s in self.sched.slots):
                if self._queue:
                    self._call("admit",
                               lambda: self.sched._admit(self._queue))
                if any(s.active for s in self.sched.slots):
                    self._call("tick", self.sched.tick)
            log(f"warm-up round {r + 1}: {dict(self.s.mesh.trace_counts)}")
            if r + 1 >= WARMUP_ROUNDS and self.s.traces() == before:
                break
        self.records.clear()
        self.ticks.clear()
        return r + 1

