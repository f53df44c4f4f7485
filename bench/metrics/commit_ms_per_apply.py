"""Verbs + compression: time in ``quantize_delta`` and ``CommitDelta``, per apply (ms)."""


def read(run):
    return run.per_apply_ms("commit")
