"""The edge step's share of its roofline: for each tick, the least time
the chip could take for the work the active rows require (the larger of
operations over peak FLOP/s and bytes over HBM bandwidth; bytes are the
edge weights, the shared exit head once and each row's keys and values),
summed over the traced window, over the edge step's device time there."""
from bench import flops, series

PROGRAMS = ("edge_step", "edge_step_masked")


def read(run):
    got = series.program_seconds(run, PROGRAMS)
    if got is None or run.peaks is None:
        return None
    layers, heads = run.exits[-1], len(run.exits)
    need = 0.0
    for t in run.ticks:
        f, b = flops.step_work(run.cfg, layers, heads, t.edge_ctx)
        need += max(f / run.peaks["bf16_flops_per_s"],
                    b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / got[1] if need else None
