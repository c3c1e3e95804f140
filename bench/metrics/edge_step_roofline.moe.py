"""``edge_step_roofline`` for a stack whose blocks differ (a dense first
block, then expert blocks): the edge partition's work is counted over its
0-based layers 0..l_ee2-1, not a count of blocks.  The least time per
tick is the larger of operations over peak FLOP/s and bytes over HBM
bandwidth; bytes are the edge weights (every held expert of each expert
layer), the shared exit head once and each row's latent cache."""
from bench import flops, series
from bench.metrics.edge_step_roofline import PROGRAMS


def read(run):
    got = series.program_seconds(run, PROGRAMS)
    if got is None or run.peaks is None:
        return None
    layers, heads = range(run.exits[-1]), len(run.exits)
    need = 0.0
    for t in run.ticks:
        f, b = flops.step_work(run.cfg, layers, heads, t.edge_ctx)
        need += max(f / run.peaks["bf16_flops_per_s"],
                    b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / got[1] if need else None
