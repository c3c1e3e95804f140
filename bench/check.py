"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished (drawn from the seed: the longest, and
one from every slot of the pool) is run through the plain reference (``bench.reference``), prompt
and served tokens together.  Each served token was produced by one head:
an exit (l_ee1 or l_ee2) or the cloud partition's final head.  Three
numbers are compared, each against its limit from the cell file:

* ``gap_exit``: the widest gap by which an exit-served token's reference
  logit lies below the reference's best logit at that exit;
* ``gap_cloud``: the same for the cloud-served tokens at the final head;
* ``gate_miss``: the widest contradiction between a token's claimed head
  and the reference's confidences, as a share of theta: an exit at l_ee1
  claims confidence1 >= theta, one at l_ee2 claims confidence1 < theta <=
  confidence2, and a cloud token claims both below theta.

The first token comes from the prompt's prefill: the scheduler records
only whether the cloud served it, so an edge-served first token is read
at the exit under which it is the more consistent, gap and gate each
measured against its limit.  Neither number alone picks the exit: the two
exits' argmaxes mostly agree, so a near tie at the serving exit leaves the
other exit with the smaller gap and a gate that contradicts by far.  A
sampled request that does not hold
exactly its ``max_new`` tokens, or holds a token outside the vocabulary,
fails the run on its own.

The control puts the reference in the program's place at fp8
(``lowp=True``): it picks its own head and token at each position of the
same prompts and tokens, and those are read against the float32
reference in the same three numbers.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import reference
from bench.weights import dims

EXIT = ("l1", "l2")
NUMBERS = ("gap_exit", "gap_cloud", "gate_miss")


def sample(records, n: int, seed: int) -> List:
    """``n`` finished requests drawn from the seed: the longest, then one
    from each slot of the pool that served any (so a fault confined to
    some rows of the batch cannot hide), then any others."""
    done = sorted((r for r in records if r.done),
                  key=lambda r: (len(r.req.prompt) + len(r.tokens),
                                 r.req.index))
    if len(done) <= n:
        return done
    rng = np.random.default_rng([seed, 3])
    picked = [done[-1]]
    by_slot = {}
    for r in done[:-1]:
        by_slot.setdefault(r.slot, []).append(r)
    for slot in rng.permutation(sorted(by_slot)):
        if len(picked) < n and picked[0].slot != slot:
            rs = by_slot[slot]
            picked.append(rs[rng.integers(len(rs))])
    rest = [r for r in done if all(r is not p for p in picked)]
    for i in rng.permutation(len(rest))[:n - len(picked)]:
        picked.append(rest[i])
    return picked


def sources_of(rec) -> List[str]:
    first = "cloud" if rec.cloud_requests else "exit"
    return [first] + list(rec.sources[1:])


def build_batch(recs, max_seq: int, max_new: int) -> Dict[str, np.ndarray]:
    b = len(recs)
    out = {"tokens": np.zeros((b, max_seq), np.int32),
           "cloud_keys": np.zeros((b, max_seq), bool),
           "wire": np.zeros((b, max_seq), bool),
           "read": np.zeros((b, max_new), np.int32),
           "valid": np.zeros((b, max_new), bool)}
    for i, r in enumerate(recs):
        p, n = len(r.req.prompt), len(r.tokens)
        seq = np.concatenate([r.req.prompt, np.asarray(r.tokens[:-1],
                                                       np.int32)])
        out["tokens"][i, :len(seq)] = seq
        out["cloud_keys"][i, :p] = True
        src = sources_of(r)
        for j in range(1, n):
            out["cloud_keys"][i, p + j - 1] = src[j] == "cloud"
        out["wire"][i, p:] = True
        out["read"][i, :n] = p - 1 + np.arange(n)
        out["valid"][i, :n] = True
    return out


def _miss(src: str, c1: float, c2: float, theta: float) -> float:
    if src == "l1":
        v = theta - c1
    elif src == "l2":
        v = max(c1 - theta, theta - c2)
    else:
        v = max(c1 - theta, c2 - theta)
    return max(0.0, v) / theta


def numbers(ref, picks: Dict[str, np.ndarray], row: int, valid, theta: float,
            limits: Dict[str, float], where: Optional[Dict] = None
            ) -> Dict[str, float]:
    """The three numbers for the tokens of token row ``row`` of the
    readings, whose heads are ``picks`` (B, N) of "l1"/"l2"/"cloud"/"exit"
    ("exit": an edge-served first token, either exit).  ``where``, if
    given, receives for each number the token that set it: its batch row,
    its position among the served tokens, the head it claims, the head it
    was read at and the reference's exit confidences over theta."""
    out = dict.fromkeys(NUMBERS, 0.0)
    c1, c2 = ref["l1"]["conf"], ref["l2"]["conf"]
    for (b, n) in zip(*np.nonzero(valid)):
        src = picks[b, n]
        heads = EXIT if src == "exit" else (src,)
        gaps = {h: float(ref[h]["best"][b, n] - ref[h]["at"][row, b, n])
                for h in heads}
        miss = {h: _miss(h, float(c1[b, n]), float(c2[b, n]), theta)
                for h in heads}
        h = min(heads, key=lambda k: max(gaps[k] / limits["gap_exit"],
                                         miss[k] / limits["gate_miss"]))
        key = "gap_cloud" if h == "cloud" else "gap_exit"
        for k, v in ((key, gaps[h]), ("gate_miss", miss[h])):
            if v > out[k]:
                out[k] = v
                if where is not None:
                    where[k] = [int(b), int(n), str(src), h,
                                float(c1[b, n]) / theta,
                                float(c2[b, n]) / theta]
    return out


def verdict(numbers: Optional[Dict[str, float]], errors: List[str],
            limits: Dict[str, float]):
    """``correct``, and each number beside its limit as the result line
    prints them: correct is no error and every number at or under its
    limit.  The program's run and the control are judged by this alone."""
    table = {k: {"value": None if numbers is None else numbers.get(k),
                 "limit": limits[k]} for k in NUMBERS}
    ok = (not errors and numbers is not None
          and all(numbers[k] <= limits[k] for k in NUMBERS))
    return bool(ok), table


def run(records, cfg: Dict, cell: Dict, traffic: Dict, seed: int, *,
        control: bool = False, log=lambda m: None) -> Dict:
    """Compare a sample of the window's finished requests with the
    reference.  Returns {"numbers": {...}, "errors": [...], "tokens": n,
    "worst": {number: [request index, token position, claimed head, read
    head, confidence1 / theta, confidence2 / theta]}} and, with
    ``control``, the control's numbers."""
    chk = cell["check"]
    theta = float(cell["theta"])
    vocab = dims(cfg)["vocab"]
    recs = sample(records, int(chk["requests"]), seed)
    errors = []
    if len(recs) < int(chk["requests"]):
        errors.append(f"only {len(recs)} requests finished; the check "
                      f"needs {chk['requests']}")
    for r in recs:
        if len(r.tokens) != r.req.max_new:
            errors.append(f"request {r.req.index}: {len(r.tokens)} tokens, "
                          f"asked for {r.req.max_new}")
        if any(not 0 <= t < vocab for t in r.tokens):
            errors.append(f"request {r.req.index}: token outside the "
                          f"vocabulary")
    if errors or not recs:
        return {"numbers": None, "errors": errors, "tokens": 0}
    max_new = int(traffic["output_len"]["max"])
    batch = build_batch(recs, int(cell["max_seq"]), max_new)
    exits = tuple(cfg["assumed"]["exit_layers"])
    served = np.zeros(batch["read"].shape, np.int32)
    picks = np.full(batch["read"].shape, "cloud", dtype=object)
    for i, r in enumerate(recs):
        served[i, :len(r.tokens)] = r.tokens
        picks[i, :len(r.tokens)] = sources_of(r)
    second: Optional[Dict[str, np.ndarray]] = None
    if control:
        low = reference.readings(cfg, seed, exits, batch, lowp=True,
                                 tokens={h: np.stack([served, served])
                                         for h in reference.HEADS}, log=log)
        second = {h: low[h]["argmax"] for h in reference.HEADS}
        lpick = np.where(low["l1"]["conf"] >= theta, "l1",
                         np.where(low["l2"]["conf"] >= theta, "l2",
                                  "cloud")).astype(object)
    ref = reference.readings(
        cfg, seed, exits, batch,
        tokens={h: np.stack([served, served if second is None
                             else second[h]]) for h in reference.HEADS},
        log=log)
    limits = chk["limits"]
    where: Dict[str, list] = {}
    out = {"numbers": numbers(ref, picks, 0, batch["valid"], theta, limits,
                              where),
           "errors": [], "tokens": int(batch["valid"].sum()),
           "worst": {k: [recs[w[0]].req.index] + w[1:]
                     for k, w in where.items()}}
    if control:
        out["control"] = numbers(ref, lpick, 1, batch["valid"], theta,
                                 limits)
    return out
