"""Verbs + compression: share of the window's quantize calls that ran as
the one grid program and one kernel dispatch (the program's
``quantize_fused`` counter) rather than an eager branch (``quantize_eager``),
in percent."""


def read(run):
    fused, eager = run.counted_per_apply("quantize_fused"), run.counted_per_apply("quantize_eager")
    if fused is None or eager is None or fused + eager == 0:
        return None
    return 100.0 * fused / (fused + eager)
