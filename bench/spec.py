"""Finds a cell's files by the names in ``BENCHMARK.json``.

    bench/cells/<workload>.json    mode, theta, slots, max_seq, check
    bench/configs/<config>.json    the model configuration as it is run
    bench/traffic/<traffic>.json   the traffic mix's parameters
    bench/metrics/<metric>.py      one reader per metric

Adding a cell adds files and one ``workloads`` entry; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric is missing or
    inconsistent."""


def _json(path: pathlib.Path) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def benchmark(root: pathlib.Path = ROOT) -> Dict:
    return _json(root / "BENCHMARK.json")


def cell(name: str, root: pathlib.Path = ROOT) -> Dict:
    """Everything one workload needs: its ``BENCHMARK.json`` entry, cell
    file, configuration, traffic mix and the names of the metrics it
    reports with tracing off (``end_to_end``) and on (``per_layer``)."""
    bm = benchmark(root)
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    c = _json(root / "bench" / "cells" / f"{name}.json")
    for k in ("config", "traffic"):
        if c[k] != entry[k]:
            raise SpecError(f"cell file says {k}={c[k]!r}, BENCHMARK.json "
                            f"says {entry[k]!r}")
    cfg = _json(root / "bench" / "configs" / f"{entry['config']}.json")
    mix = _json(root / "bench" / "traffic" / f"{entry['traffic']}.json")

    def names(kind: str) -> List[str]:
        return [m["name"] for m in bm[kind]
                if name in m.get("workloads", [name])]

    return {"name": name, "entry": entry, "cell": c, "config": cfg,
            "traffic": mix, "end_to_end": names("end_to_end"),
            "per_layer": names("per_layer"),
            "units": {m["name"]: m["unit"]
                      for m in bm["end_to_end"] + bm["per_layer"]}}


def reader(metric: str, root: pathlib.Path = ROOT):
    """The module ``bench/metrics/<metric>.py``; its ``read(run)`` returns
    the metric's value, or None where the run holds nothing to read.  A
    reader of device time by named scope sets ``SCOPES = True``: only
    then does a traced run fill ``run.trace["scopes"]`` (``trace.scopes``,
    some tens of seconds of xprof conversion)."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.exists():
        raise SpecError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
