"""The benchmark's general code: one harness, driven by the data files
under ``bench/configs``, ``bench/traffic``, ``bench/cells``, and the
modules under ``bench/models``, ``bench/metrics`` and ``bench/kernels``."""
