"""The whole served path's share of the chip's peak: operations the
window's work requires (each decoded token through the edge layers and
both exits, each cloud-served token through the cloud layers and the
head, each admitted prompt through the partitions its mode runs; no
padding), over the host time spent in admissions and ticks times the
peak."""
from bench import flops


def read(run):
    if run.peaks is None:
        return None
    l1, l2 = run.exits[0], run.exits[-1]
    cloud_layers = run.layers - l1
    mode = run.cell["mode"]
    work, busy = 0.0, 0.0
    for t in run.ticks:
        busy += t.end - t.start
        work += flops.step_work(run.cfg, l2, 2, t.edge_ctx)[0]
        work += flops.step_work(run.cfg, cloud_layers, 1, t.cloud_ctx)[0]
        for p in t.prompts:
            work += flops.prefill_flops(run.cfg, l2, 2, p)
            if mode == "collm":
                work += flops.prefill_flops(run.cfg, cloud_layers, 1, p)
    if not work or not busy:
        return None
    return 100.0 * work / (busy * run.peaks["bf16_flops_per_s"])
