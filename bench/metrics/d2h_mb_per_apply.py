"""Host-device transfers: bytes copied from the device to the host (the
program's ``d2h_bytes`` counter), per apply (MB, 1e6 bytes)."""


def read(run):
    n = run.counted_per_apply("d2h_bytes")
    return None if n is None else n / 1e6
