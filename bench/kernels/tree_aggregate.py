"""Weighted aggregation of C children per group (``tree_aggregate_groups``).

A call with grads (G, C, L) and weights (G, C) needs each of the G*C*L
values read once, the G*C weights read once and the G*L sums written
once, with one multiply and one add per value.  L is the unpadded
length the wrapper is given (256 on the quantized apply path), not the
1,024-wide tile the Pallas branch pads it to.
"""
CALL = "tree_aggregate_groups"
TRACE = r"^jit_tree_aggregate_groups/"


def cost(args, kwargs) -> tuple[float, float]:
    (shape, itemsize), (wshape, witem) = args[0], args[1]
    g, c, length = shape
    flops = 2.0 * g * c * length
    nbytes = g * c * length * itemsize + g * c * witem + g * length * 4
    return flops, float(nbytes)
