"""Host-device transfers: bytes uploaded from the host to the device (the
program's ``h2d_bytes`` counter), per apply (MB, 1e6 bytes)."""


def read(run):
    n = run.counted_per_apply("h2d_bytes")
    return None if n is None else n / 1e6
