"""One general traffic generator, driven by a mix's data file.

A mix (``bench/traffic/<name>.json``) gives the loop ("open" or "closed"),
the arrival process of an open loop, the length distributions and a
``sizes_seed``.  The sizes (prompt and output lengths, in order) and the
arrival times of an open loop are drawn once from ``sizes_seed``, so every
run seed serves the same arrivals with the same sizes; the run seed draws
the prompt tokens (and, elsewhere, the weights).  Permuting the sizes by
seed was measured to change the queueing, and with it the tail, far more
than two runs of one seed differ.

The arrival arithmetic is copied from the program's
``core/workload.py`` (``ArrivalProcess``/``arrival_times``, Poisson or
gamma interarrivals with mean 1, scaled by the rate) so that later
changes to the program cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the harness sends it.  ``due`` is seconds after the
    window opens (open loop) or None until its client sends it (closed
    loop)."""
    index: int
    prompt: np.ndarray
    max_new: int
    client: int = 0
    due: Optional[float] = None


def _unit_gaps(kind: str, cv2: float, n: int, rng: random.Random
               ) -> List[float]:
    """``n`` interarrival gaps with mean 1: exponential (Poisson) or gamma
    with squared coefficient of variation ``cv2``."""
    if kind == "gamma" and cv2 != 1.0:
        k, theta = 1.0 / cv2, cv2
        return [rng.gammavariate(k, theta) for _ in range(n)]
    if kind not in ("poisson", "gamma"):
        raise ValueError(f"unknown arrival kind {kind!r}")
    return [rng.expovariate(1.0) for _ in range(n)]


def _lengths(spec: Dict, n: int, rng: random.Random) -> List[int]:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return [rng.randint(lo, hi) for _ in range(n)]
    if spec["dist"] == "lognormal":
        mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
        return [min(hi, max(lo, int(round(rng.lognormvariate(mu, sigma)))))
                for _ in range(n)]
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def warmup_lengths(spec: Dict) -> List[int]:
    """Prompt lengths that reach every power-of-two length bucket a mix's
    clip range can fall into: both ends and each side of every power of
    two between them."""
    lo, hi = int(spec["min"]), int(spec["max"])
    out = {lo, hi}
    b = 1
    while b <= hi:
        out.update(x for x in (b, b + 1) if lo <= x <= hi)
        b *= 2
    return sorted(out)


class Traffic:
    """The requests of one run: ``open_loop`` requests carry due times;
    closed-loop clients draw their next request from ``next_for``."""

    def __init__(self, mix: Dict, *, seed: int, vocab: int, slots: int,
                 rate: Optional[float] = None):
        self.mix = mix
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop must be open or closed, got {self.loop}")
        pool = int(mix["pool"])
        size_rng = random.Random(int(mix["sizes_seed"]))
        prompts = _lengths(mix["prompt_len"], pool, size_rng)
        outputs = _lengths(mix["output_len"], pool, size_rng)
        self.sizes = list(zip(prompts, outputs))
        self.tok_rng = np.random.default_rng([seed, 1])
        self.vocab = vocab
        self._next = 0
        self.clients = slots if self.loop == "closed" else 0
        self.gaps: List[float] = []
        if self.loop == "open":
            arr = mix["arrival"]
            rate = float(rate if rate is not None else arr["rate"])
            if rate <= 0:
                raise ValueError("arrival rate must be > 0")
            gaps = _unit_gaps(arr["kind"], float(arr.get("cv2", 1.0)), pool,
                              size_rng)
            self.gaps = [g / rate for g in gaps]

    def _make(self, client: int = 0, due: Optional[float] = None) -> Req:
        if self._next >= len(self.sizes):
            raise RuntimeError(f"traffic pool of {len(self.sizes)} requests "
                               f"exhausted; raise 'pool' in the mix file")
        p_len, n_out = self.sizes[self._next]
        prompt = self.tok_rng.integers(0, self.vocab, p_len).astype(np.int32)
        r = Req(index=self._next, prompt=prompt, max_new=n_out,
                client=client, due=due)
        self._next += 1
        return r

    def open_loop(self, seconds: float) -> List[Req]:
        """Every request due within ``seconds`` (the first at its first
        gap, as ``arrival_times`` stamps them)."""
        out, t = [], 0.0
        for g in self.gaps:
            t += g
            if t >= seconds:
                break
            out.append(self._make(due=t))
        return out

    def first_closed(self) -> List[Req]:
        """Each client's first request, spread over ``stagger_s`` so the
        slots do not all admit in the window's first instant."""
        spread = float(self.mix.get("stagger_s", 0.0))
        return [self._make(client=c, due=spread * c / max(1, self.clients))
                for c in range(self.clients)]

    def next_for(self, client: int, due: float) -> Req:
        return self._make(client=client, due=due)
