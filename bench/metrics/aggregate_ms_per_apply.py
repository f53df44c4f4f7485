"""Verbs + compression: time in ``ApplyBuffered`` and the broadcast-state
build, per apply (ms)."""


def read(run):
    return run.per_apply_ms("aggregate")
