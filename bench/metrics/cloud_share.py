"""Share of the tokens of the window's completed requests that the cloud
partition served (the gate's verdict below theta at both exits)."""
from bench import series


def read(run):
    done = series.completed(run)
    total = sum(len(r.tokens) for r in done)
    if not total:
        return None
    cloud = sum((1 if r.cloud_requests else 0)
                + sum(1 for s in r.sources[1:] if s == "cloud")
                for r in done)
    return 100.0 * cloud / total
