"""Mean share of the pool's slots holding a request, over the window's
ticks."""


def read(run):
    ticks = [t for t in run.ticks if t.kind == "tick"]
    if not ticks:
        return None
    return 100.0 * sum(t.active for t in ticks) / (len(ticks)
                                                    * int(run.cell["slots"]))
