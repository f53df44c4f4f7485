"""Kernels: the quantize kernel's share of its roofline (%)."""


def read(run):
    return run.roofline("qsgd_quantize")
