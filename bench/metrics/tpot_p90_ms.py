"""Time per output token, p90 over every request the window completed of
(t_last - t_first) / (n - 1) on the host stamps."""
from bench import series


def read(run):
    return series.p90([(r.stamps[-1] - r.stamps[0]) * 1e3
                       / (len(r.stamps) - 1)
                       for r in series.completed(run) if len(r.stamps) > 1])
