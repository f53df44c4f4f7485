"""Kernels: the aggregation kernel's share of its roofline (%), bytes
counted at the unpadded row length."""


def read(run):
    return run.roofline("tree_aggregate")
