"""Process start to the window's first due request: weights, the
scheduler's pools and the warm-up that compiles every program."""


def read(run):
    return run.setup_s
