"""The benchmark's general code: one harness, driven by the data files
under ``bench/configs``, ``bench/traffic``, ``bench/cells``,
``bench/metrics`` and ``bench/kernels``."""
