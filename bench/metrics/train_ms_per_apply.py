"""FL engine: time inside ``engine.fused_local_training``, per apply (ms)."""


def read(run):
    return run.per_apply_ms("train")
