"""Output tokens committed in the window over the window's seconds."""


def read(run):
    n = sum(1 for r in run.records for t in r.stamps if run.in_window(t))
    return n / run.seconds if n else None
