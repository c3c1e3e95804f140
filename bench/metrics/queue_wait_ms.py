"""p90 of due time to admission, over the requests admitted in the
window (host stamps taken after the admission call)."""
from bench import series


def read(run):
    return series.p90([(r.admitted - r.due) * 1e3 for r in run.records
                       if r.admitted is not None
                       and run.in_window(r.admitted)])
