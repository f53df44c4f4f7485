"""FL engine and below: XLA programs built inside the window (should be 0)."""


def read(run):
    return float(run.compiles)
