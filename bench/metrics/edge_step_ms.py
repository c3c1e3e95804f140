"""Device milliseconds per call of the edge partition's batched decode
step (layers 1..l_ee2 and both exits)."""
from bench import series

PROGRAMS = ("edge_step", "edge_step_masked")


def read(run):
    got = series.program_seconds(run, PROGRAMS)
    return None if got is None else 1e3 * got[1] / got[0]
