"""Device milliseconds of prompt prefill per request admitted in the
traced window: the edge and cloud prefill programs together."""
from bench import series

PROGRAMS = ("edge_prefill_padded", "cloud_prefill_padded",
            "full_prefill_padded")


def read(run):
    got = series.program_seconds(run, PROGRAMS)
    admitted = sum(len(t.prompts) for t in run.ticks)
    if got is None or not admitted:
        return None
    return 1e3 * got[1] / admitted
