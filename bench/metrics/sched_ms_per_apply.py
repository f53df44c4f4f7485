"""Event core + scheduler: window wall time outside the apply spans, per apply (ms)."""


def read(run):
    if not run.n:
        return None
    return 1e3 * (run.window_s - run.span_s("apply")) / run.n
