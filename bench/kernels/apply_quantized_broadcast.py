"""Fused dequantize-and-apply of a broadcast delta chain
(``apply_quantized_broadcast``).

A call with held state (R, 256) float32 and a chain of D int8 deltas
(D, R, 256) with scales (D, R, 1) reads each once and writes the new
(R, 256) state; per delta value one multiply and one add.
"""
CALL = "apply_quantized_broadcast"
TRACE = r"^jit_apply_quantized_broadcast/"


def cost(args, kwargs) -> tuple[float, float]:
    (wshape, witem), (qshape, qitem), (sshape, sitem) = args[0], args[1], args[2]
    d, rows, width = qshape
    n = rows * width
    nbytes = n * witem + d * n * qitem + d * rows * sitem + n * 4
    return 2.0 * d * n, float(nbytes)
