"""Event core + scheduler: self time of the program's ``event`` spans (one
event callback, less the apply, verbs and transfers inside it), per apply (ms)."""


def read(run):
    return run.self_ms_per_apply("event")
