"""Device milliseconds per call of the cloud partition's batched decode
step (layers l_ee1+1..L and the output head)."""
from bench import series

PROGRAMS = ("cloud_step_masked", "ring_cloud_steps")


def read(run):
    got = series.program_seconds(run, PROGRAMS)
    return None if got is None else 1e3 * got[1] / got[0]
