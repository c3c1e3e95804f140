"""Helpers the metric readers share: the window's requests and calls."""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def p90(values: Sequence[float]) -> Optional[float]:
    return float(np.percentile(values, 90)) if len(values) else None


def first_tokens(run) -> List:
    """Requests whose first token the window saw."""
    return [r for r in run.records if r.stamps and run.in_window(r.stamps[0])]


def completed(run) -> List:
    """Requests the window finished."""
    return [r for r in run.records
            if r.done and r.stamps and run.in_window(r.stamps[-1])]


def program_seconds(run, names: Sequence[str]):
    """(calls, device seconds) of the named programs in the trace, or
    None without a trace or without any such call."""
    if run.trace is None:
        return None
    progs = run.trace["programs"]
    n = sum(progs[k]["count"] for k in names if k in progs)
    s = sum(progs[k]["seconds"] for k in names if k in progs)
    return (n, s) if n else None
