"""Trace-driven fleet simulation: adaptive control vs. static defaults
(docs/fleet_sim.md).

Two sweeps, both replaying bursty open-loop arrival traces
(``workload.ArrivalProcess``: gamma interarrivals + a diurnal ramp)
through the serving engine in virtual time, so every gated number is
deterministic:

``--fleet-window`` — N single-slot edge engines (``generate_multi``)
share one batching ``CloudServicePoint``.  ``static`` fixes the
accumulation window at the throughput bench's 4ms default; ``adaptive``
attaches a ``WindowController`` that sizes the window from the observed
request rate — 0 in the troughs (the window is pure latency tax when
nothing coalesces), ~(max_batch-1) mean gaps in the bursts.  Same
prompts, same arrivals, same service physics: the streams are
token-identical and only the latency distribution moves.

``--adaptive-pool`` — one 8-request fleet drains through a 4-slot paged
engine whose page budget is ~60% of worst-case demand, so bursts force
preemptions.  Both arms share one ``ResumeCostModel`` (resume costs are
billed into the virtual clock either way); ``static`` fixes
``preemption="recompute"`` with a zero watermark, ``adaptive`` adds the
engine-side ``AdaptiveController`` — watermark AIMD on observed
preemption/OutOfPages pressure, the fluid-ODE admission gate, and the
per-victim swap-vs-recompute choice priced by the shared cost model.

With ``--check`` each sweep asserts the adaptive arm beats (or ties)
the static defaults on p99 per-token latency AND SLO attainment at
equal token output; rows land in ``--json`` (BENCH_fleet.json).

    PYTHONPATH=src:. python benchmarks/fleet_bench.py --check
    PYTHONPATH=src:. python benchmarks/fleet_bench.py --fleet-window --check
    PYTHONPATH=src:. python benchmarks/fleet_bench.py --adaptive-pool --check
"""
from __future__ import annotations

import argparse
import json

from repro.core.collm import CollmConfig
from repro.core.transport import AsyncSimChannel, CloudServicePoint
from repro.core.workload import ArrivalProcess, arrival_times
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.adaptive import (AdaptiveConfig, ResumeCostModel,
                                    WindowController)
from repro.serving.engine import ServingSystem

from benchmarks.common import PAPER_NET, tiny_trained_model

TICK_TIME_S = 0.01           # virtual edge compute per decode tick
CLOUD_SERVICE_S = 0.008      # one batched cloud service step
STATIC_WINDOW_S = 0.004      # the throughput bench's fixed default

# bursty day/night trace: clumped gamma arrivals (cv^2=4) riding a
# diurnal ramp — dense bursts where coalescing pays, sparse troughs
# where a fixed window is pure tax
FLEET_ARRIVALS = ArrivalProcess(rate=14.0, kind="gamma", cv2=4.0,
                                diurnal_amp=0.6, diurnal_period_s=1.2)
# per-stream SLOs (virtual s): TTFT from open-loop arrival to first
# token (queueing included), mean inter-token gap target
SLO_TTFT_S = 0.6
SLO_TPOT_S = 0.030


def _stat_row(name: str, r: dict) -> dict:
    st = r["stats"]
    return {
        "arm": name,
        "tokens": int(st.tokens),
        "virtual_s": r["virtual_time"],
        "ttft_p50_s": st.ttft_p(50), "ttft_p99_s": st.ttft_p(99),
        "token_lat_p50_s": st.token_lat_p(50),
        "token_lat_p99_s": st.token_lat_p(99),
        "slo_attainment": st.slo_attainment,
        "slo_total": st.slo_total, "slo_met": st.slo_met,
        "preemption_rate": st.preemption_rate,
        "deadline_miss_rate": st.deadline_miss_rate,
    }


def _print_row(row: dict) -> None:
    print(f"{row['arm']},{row['tokens']},{row['virtual_s']:.3f},"
          f"{1e3 * row['ttft_p50_s']:.1f},{1e3 * row['ttft_p99_s']:.1f},"
          f"{1e3 * row['token_lat_p50_s']:.2f},"
          f"{1e3 * row['token_lat_p99_s']:.2f},"
          f"{row['slo_attainment']:.3f},{row['preemption_rate']:.3f}")


def _check_adaptive_beats_static(sweep: str, static: dict,
                                 adaptive: dict) -> None:
    assert adaptive["tokens_equal"], (
        f"{sweep}: adaptive control must be token-invisible (same streams "
        f"as the static arm)")
    assert adaptive["token_lat_p99_s"] <= static["token_lat_p99_s"], (
        f"{sweep}: adaptive p99 token latency "
        f"{1e3 * adaptive['token_lat_p99_s']:.2f}ms should beat static "
        f"{1e3 * static['token_lat_p99_s']:.2f}ms")
    assert adaptive["slo_attainment"] >= static["slo_attainment"], (
        f"{sweep}: adaptive SLO attainment {adaptive['slo_attainment']:.3f} "
        f"should be >= static {static['slo_attainment']:.3f}")


# ---------------------------------------------------------------------------
# Sweep A: adaptive cloud batch window across a fleet of edge engines
# ---------------------------------------------------------------------------
def run_fleet_window(*, n_engines: int = 4, n_requests: int = 12,
                     max_new: int = 16, theta: float = 0.8, seed: int = 0,
                     check: bool = False, rows: list = None) -> dict:
    """N single-slot engines + one shared batching cloud, static 4ms
    accumulation window vs. rate-adaptive ``WindowController``, replaying
    the same bursty arrival trace."""
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = [data.sample_tokens(12) for _ in range(n_requests)]
    arrivals = arrival_times(FLEET_ARRIVALS, n_requests, seed=seed)

    out: dict = {}
    print("# fleet-window sweep: gamma cv2=4 + diurnal arrivals, "
          f"{n_engines} engines, shared cloud ({CLOUD_SERVICE_S * 1e3:.0f}ms "
          "service)")
    print("arm,tokens,virtual_s,ttft_p50_ms,ttft_p99_ms,lat_p50_ms,"
          "lat_p99_ms,slo_attainment,preempt_rate")
    for arm in ("static", "adaptive"):
        ctrl = (WindowController(max_window_s=STATIC_WINDOW_S)
                if arm == "adaptive" else None)
        svc = CloudServicePoint(CLOUD_SERVICE_S,
                                batch_window_s=STATIC_WINDOW_S,
                                max_batch=n_engines,
                                window_controller=ctrl)
        chans = [AsyncSimChannel(PAPER_NET, service=svc)
                 for _ in range(n_engines)]
        sysm = ServingSystem(model, params, CollmConfig(theta=theta))
        r = sysm.generate_multi(prompts, max_new, n_engines=n_engines,
                                channels=chans, tick_time_s=TICK_TIME_S,
                                arrivals=arrivals, slo_ttft_s=SLO_TTFT_S,
                                slo_tpot_s=SLO_TPOT_S)
        row = dict(_stat_row(arm, r), mode="fleet_window",
                   n_engines=n_engines, n_requests=n_requests,
                   max_new=max_new,
                   window_adjustments=(ctrl.adjustments if ctrl else 0),
                   cloud_batches=svc.batches)
        out[arm] = dict(row, tokens_list=r["tokens"])
        if rows is not None:
            rows.append(row)
        _print_row(row)
    out["adaptive"]["tokens_equal"] = (
        out["adaptive"]["tokens_list"] == out["static"]["tokens_list"])

    if check:
        _check_adaptive_beats_static("fleet-window", out["static"],
                                     out["adaptive"])
        assert out["adaptive"]["window_adjustments"] > 0, \
            "the window controller never adjusted the window"
        print(f"# check passed: adaptive window p99 "
              f"{1e3 * out['adaptive']['token_lat_p99_s']:.2f}ms <= static "
              f"{1e3 * out['static']['token_lat_p99_s']:.2f}ms, SLO "
              f"{out['adaptive']['slo_attainment']:.3f} >= "
              f"{out['static']['slo_attainment']:.3f}; streams identical")
    return out


# ---------------------------------------------------------------------------
# Sweep B: adaptive paged-pool control on an oversubscribed engine
# ---------------------------------------------------------------------------
POOL_SLOTS = 4
POOL_FRAC = 0.6              # page budget vs. worst-case demand
# shared resume physics: modest host link so swap-vs-recompute actually
# crosses over with context length instead of one mode dominating
RESUME_COST = ResumeCostModel(d0_s=0.004, d1_s=2.0e-4, host_bw=2.0e7)
POOL_ARRIVALS = ArrivalProcess(rate=30.0, kind="gamma", cv2=4.0,
                               diurnal_amp=0.5, diurnal_period_s=0.8)


def run_adaptive_pool(*, n_requests: int = 8, max_new: int = 16,
                      theta: float = 0.8, seed: int = 0,
                      check: bool = False, rows: list = None) -> dict:
    """Oversubscribed paged engine under a bursty open-loop trace:
    static (fixed recompute resume, zero watermark) vs. adaptive
    (watermark AIMD + fluid admission gate + per-victim resume mode),
    both billing resume costs from the SAME ``ResumeCostModel``."""
    tiny = tiny_trained_model()
    model, params, data = tiny["model"], tiny["params"], tiny["data"]
    prompts = [data.sample_tokens(12) for _ in range(n_requests)]
    arrivals = arrival_times(POOL_ARRIVALS, n_requests, seed=seed)
    ps = CollmConfig(kv_layout="paged").page_size
    worst = max((len(p) + max_new - 1) // ps + 1 for p in prompts)
    budget = max(worst, int(POOL_FRAC * POOL_SLOTS * worst))

    out: dict = {}
    print(f"# adaptive-pool sweep: {POOL_SLOTS} slots, {budget} pages "
          f"(~{100 * POOL_FRAC:.0f}% of worst-case), bursty arrivals")
    print("arm,tokens,virtual_s,ttft_p50_ms,ttft_p99_ms,lat_p50_ms,"
          "lat_p99_ms,slo_attainment,preempt_rate")
    for arm in ("static", "adaptive"):
        pre = "recompute" if arm == "static" else "swap"
        sysv = ServingSystem(model, params,
                             CollmConfig(theta=theta, kv_layout="paged",
                                         preemption=pre))
        kw = dict(num_slots=POOL_SLOTS, num_pages=budget,
                  tick_time_s=TICK_TIME_S, arrivals=arrivals,
                  slo_ttft_s=SLO_TTFT_S, slo_tpot_s=SLO_TPOT_S,
                  resume_cost=RESUME_COST)
        if arm == "adaptive":
            kw["adaptive"] = AdaptiveConfig()
        r = sysv.generate(prompts, max_new, mode="collm", **kw)
        row = dict(_stat_row(arm, r), mode="adaptive_pool",
                   slots=POOL_SLOTS, pages=budget, n_requests=n_requests,
                   max_new=max_new, preemptions=r["preemptions"],
                   oops=r["oops"], adaptive=r["adaptive"])
        out[arm] = dict(row, tokens_list=r["tokens"])
        if rows is not None:
            rows.append(row)
        _print_row(row)
    out["adaptive"]["tokens_equal"] = (
        out["adaptive"]["tokens_list"] == out["static"]["tokens_list"])

    if check:
        _check_adaptive_beats_static("adaptive-pool", out["static"],
                                     out["adaptive"])
        assert out["static"]["preemptions"] >= 1, (
            f"the {budget}-page budget should force at least one "
            f"preemption in the static arm")
        print(f"# check passed: adaptive pool p99 "
              f"{1e3 * out['adaptive']['token_lat_p99_s']:.2f}ms <= static "
              f"{1e3 * out['static']['token_lat_p99_s']:.2f}ms, SLO "
              f"{out['adaptive']['slo_attainment']:.3f} >= "
              f"{out['static']['slo_attainment']:.3f}; "
              f"{out['static']['preemptions']} vs "
              f"{out['adaptive']['preemptions']} preemptions; streams "
              f"identical")
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--engines", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--theta", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="assert adaptive beats static on p99 token "
                         "latency + SLO attainment at equal token output")
    ap.add_argument("--json", default="BENCH_fleet.json",
                    help="machine-readable sweep rows")
    ap.add_argument("--fleet-window", action="store_true",
                    help="run only the shared-cloud window sweep")
    ap.add_argument("--adaptive-pool", action="store_true",
                    help="run only the oversubscribed paged-pool sweep")
    args = ap.parse_args()
    both = not (args.fleet_window or args.adaptive_pool)
    rows: list = []
    if args.fleet_window or both:
        run_fleet_window(n_engines=args.engines, n_requests=args.requests,
                         max_new=args.max_new, theta=args.theta,
                         seed=args.seed, check=args.check, rows=rows)
    if args.adaptive_pool or both:
        run_adaptive_pool(n_requests=min(args.requests, 8),
                          max_new=args.max_new, theta=args.theta,
                          seed=args.seed, check=args.check, rows=rows)
    with open(args.json, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {len(rows)} rows to {args.json}")


if __name__ == "__main__":
    main()
