"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this process
holds, and prints as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``check`` (each number compared with the
reference, beside its limit).  The same numbers end standard error.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.

Options for measuring the benchmark itself, never used by its checks:
``--rate`` overrides an open-loop mix's arrival rate (a knee sweep), and
``--control 1`` also runs the fp8 control and prints its verdict on a
``control`` line, judged as the program's run is.
Progress goes to standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
CACHE = ROOT / ".bench_cache" / "jax"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec
    try:
        c = spec.cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    from bench import harness
    info = harness.device_info()
    chips = int(c["entry"]["chips"])
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{info['count']} {info['platform']} device(s); nothing was "
              f"run", file=sys.stderr)
        return 2
    peaks = harness.peaks_for(info["kind"])
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    out = harness.run(c, seed=args.seed, seconds=args.seconds,
                      trace_on=bool(args.trace), t_start=T_START,
                      control=bool(args.control), rate=args.rate,
                      peaks=peaks,
                      log=lambda m: print(f"bench: {m}", file=sys.stderr))
    print("info " + json.dumps(out["info"]), flush=True)
    if "control" in out["info"]:
        print("control " + json.dumps(out["info"]["control"]), flush=True)
        print("control " + json.dumps(out["info"]["control"]),
              file=sys.stderr)
    for err in out["info"]["errors"]:
        print(f"bench: {err}", file=sys.stderr)
    for name, v in out["result"]["check"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
