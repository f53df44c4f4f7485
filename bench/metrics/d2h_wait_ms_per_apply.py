"""Host-device transfers: self time of the program's ``xfer.d2h`` spans,
the blocking device-to-host copies with the wait for the programs that
produce them, per apply (ms)."""


def read(run):
    return run.self_ms_per_apply("xfer.d2h")
