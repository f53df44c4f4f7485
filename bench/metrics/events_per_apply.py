"""Event core: scheduler events dispatched in the window, per apply."""


def read(run):
    return run.events / run.n if run.n else None
