"""``cloud_step_roofline`` for a stack whose blocks differ: the cloud
partition's work is counted over its 0-based layers l_ee1..L-1."""
from bench import flops, series
from bench.metrics.cloud_step_roofline import PROGRAMS


def read(run):
    got = series.program_seconds(run, PROGRAMS)
    if got is None or run.peaks is None:
        return None
    layers = range(run.exits[0], run.layers)
    need = 0.0
    for t in run.ticks:
        f, b = flops.step_work(run.cfg, layers, 1, t.cloud_ctx)
        need += max(f / run.peaks["bf16_flops_per_s"],
                    b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / got[1] if need else None
