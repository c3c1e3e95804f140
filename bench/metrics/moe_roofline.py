"""The expert layers' share of their roofline in the two decode steps:
the least time of every call of the edge and cloud decode programs in
the traced window (each expert layer of a call: the router, the shared
experts and each row's expected share of held experts for the call's
mean rows, with the router, the shared and the held experts read once:
``moe_work`` of the architecture's adapter), over the device time of
the named scope ``moe`` in those programs.  Calls, not ticks, carry the
weights' bytes: the trace's calls and scope seconds come from the same
recorded events, while a window has ticks that stamp tokens without a
recorded decode call."""
from bench import arch, series
from bench.metrics.cloud_step_roofline import PROGRAMS as CLOUD
from bench.metrics.edge_step_roofline import PROGRAMS as EDGE

SCOPES = True
SCOPE = "moe"


def scope_seconds(run, programs, scope):
    """Device seconds of ``scope`` in ``programs``, or None."""
    got = (run.trace or {}).get("scopes")
    if not got:
        return None
    s = sum(got.get(f"{p}:{scope}", 0.0) for p in programs)
    return s or None


def least_time(run, layers_of, work):
    """Least time of the edge and cloud decode calls of the traced window:
    for each step, its calls x ``layers_of(its 0-based layers)`` x the
    larger of operations over peak FLOP/s and bytes over HBM bandwidth of
    ``work(contexts, calls)``, one layer of a call on average over the
    window's decoded rows (their contexts)."""
    fl, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    need = 0.0
    for programs, layers, ctxs in (
            (EDGE, range(run.exits[-1]),
             [c for t in run.ticks for c in t.edge_ctx]),
            (CLOUD, range(run.exits[0], run.layers),
             [c for t in run.ticks for c in t.cloud_ctx])):
        got = series.program_seconds(run, programs)
        if got is None or not ctxs:
            continue
        f, b = work(ctxs, got[0])
        need += got[0] * layers_of(layers) * max(f / fl, b / bw)
    return need


def read(run):
    a = arch.of(run.cfg)
    secs = scope_seconds(run, EDGE + CLOUD, SCOPE)
    if secs is None or run.peaks is None or not hasattr(a, "moe_work"):
        return None
    m = a.dims(run.cfg)
    need = least_time(run, lambda layers: a.moe_layers(m, layers),
                      lambda ctxs, calls: a.moe_work(m, len(ctxs) / calls))
    return 100.0 * need / secs if need else None
