"""One cost model per kernel: ``CALL`` (the ``kernels.ops`` wrapper the
probes record), ``TRACE`` (a pattern for the kernel's device operations
in the profiler trace: every operation of the jitted program that holds
the kernel, so the time never leaves out part of its work) and ``cost(args, kwargs) -> (operations, bytes)``
for one call, from the unpadded shapes the wrapper was called with."""
