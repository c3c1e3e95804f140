"""``step_mfu`` for a stack whose blocks differ: the edge partition's
work over 0-based layers 0..l_ee2-1 and the cloud's over l_ee1..L-1,
each decoded token and admitted prompt as ``step_mfu`` counts them, over
the host time in admissions and ticks times the peak."""
from bench import flops


def read(run):
    if run.peaks is None:
        return None
    edge, cloud = range(run.exits[-1]), range(run.exits[0], run.layers)
    mode = run.cell["mode"]
    work, busy = 0.0, 0.0
    for t in run.ticks:
        busy += t.end - t.start
        work += flops.step_work(run.cfg, edge, 2, t.edge_ctx)[0]
        work += flops.step_work(run.cfg, cloud, 1, t.cloud_ctx)[0]
        for p in t.prompts:
            work += flops.prefill_flops(run.cfg, edge, 2, p)
            if mode == "collm":
                work += flops.prefill_flops(run.cfg, cloud, 1, p)
    if not work or not busy:
        return None
    return 100.0 * work / (busy * run.peaks["bf16_flops_per_s"])
