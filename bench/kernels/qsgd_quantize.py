"""QSGD stochastic rounding of (R, 256) rows to int8 (``qsgd_quantize``).

A call reads the R*256 values and as many uniforms, and writes R*256
int8 lattice points and R float32 scales.  Per value: an absolute
value, a share of the row maximum, a division, an addition and a floor
(5 operations).
"""
CALL = "qsgd_quantize"
TRACE = r"^jit_qsgd_quantize/"


def cost(args, kwargs) -> tuple[float, float]:
    (xshape, xitem), (rshape, ritem) = args[0], args[1]
    rows, width = xshape
    n = rows * width
    return 5.0 * n, float(n * xitem + n * ritem + n * 1 + rows * 4)
