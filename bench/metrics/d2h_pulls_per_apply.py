"""Host-device transfers: device arrays copied to the host (the program's
``d2h_pulls`` counter), per apply."""


def read(run):
    return run.counted_per_apply("d2h_pulls")
