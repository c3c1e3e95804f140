"""Latent attention's share of its roofline in the two decode steps: the
least time of every call of the edge and cloud decode programs in the
traced window (each layer of a call: the absorbed projections and scores
of the call's mean rows, with the attention weights read once and each
row's latent cache over its context: ``mla_decode_work`` of the
architecture's adapter), over the device time of the named scope ``mla``
in those programs.  Counted per call as ``moe_roofline`` is."""
from bench import arch
from bench.metrics.cloud_step_roofline import PROGRAMS as CLOUD
from bench.metrics.edge_step_roofline import PROGRAMS as EDGE
from bench.metrics.moe_roofline import least_time, scope_seconds

SCOPES = True
SCOPE = "mla"


def read(run):
    a = arch.of(run.cfg)
    secs = scope_seconds(run, EDGE + CLOUD, SCOPE)
    if secs is None or run.peaks is None or not hasattr(
            a, "mla_decode_work"):
        return None
    m = a.dims(run.cfg)
    need = least_time(run, len,
                      lambda ctxs, calls: a.mla_decode_work(m, ctxs, calls))
    return 100.0 * need / secs if need else None
