"""Verbs + compression: self time of the program's ``quantize`` spans
(commit and broadcast quantization, their dispatches), per apply (ms)."""


def read(run):
    return run.self_ms_per_apply("quantize")
