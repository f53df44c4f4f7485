"""FL engine: self time of the program's ``train.pack`` span (the shards'
host packing and upload dispatch), per apply (ms)."""


def read(run):
    return run.self_ms_per_apply("train.pack")
