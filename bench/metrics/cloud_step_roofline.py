"""The cloud step's share of its roofline: for each tick that served the
cloud, the least time the chip could take for the cloud rows' work (cloud
weights, the output head and the keys and values each row holds in the
cloud), summed over the traced window, over the cloud step's device time
there."""
from bench import flops, series

PROGRAMS = ("cloud_step_masked", "ring_cloud_steps")


def read(run):
    got = series.program_seconds(run, PROGRAMS)
    if got is None or run.peaks is None:
        return None
    layers = run.layers - run.exits[0]
    need = 0.0
    for t in run.ticks:
        f, b = flops.step_work(run.cfg, layers, 1, t.cloud_ctx)
        need += max(f / run.peaks["bf16_flops_per_s"],
                    b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / got[1] if need else None
