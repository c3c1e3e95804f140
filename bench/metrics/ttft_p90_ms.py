"""Time to first token, p90 over every request whose first token came in
the window: from the request's due time to the host stamp taken when the
loop first saw that token committed."""
from bench import series


def read(run):
    v = series.p90([(r.stamps[0] - r.due) * 1e3
                    for r in series.first_tokens(run)])
    return v
