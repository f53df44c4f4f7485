"""FL engine: self time of the program's ``apply`` and ``replicate`` spans
(params update, snapshots, the record, master-state replication), per apply (ms)."""


def read(run):
    return run.self_ms_per_apply("apply", "replicate")
