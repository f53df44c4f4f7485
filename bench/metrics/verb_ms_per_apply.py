"""Verbs + compression: self time of the program's ``verb.commit`` and
``verb.apply`` spans (``CommitDelta``, ``ApplyBuffered``), per apply (ms)."""


def read(run):
    return run.self_ms_per_apply("verb.commit", "verb.apply")
