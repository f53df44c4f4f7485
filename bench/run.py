"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks
for (``BENCHMARK.json``).  The run builds the cell's deployment from the
seed, warms up, measures for ``--seconds`` and prints one JSON object as
the last line of standard output: ``correct`` (the program against the
plain reference, ``bench/lib/reference.py``), ``attempted`` / ``failed``
(applies in the window), ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones read from a profiler trace of the
window), ``device`` and, last, ``checks``: each number compared with its
limit.  Without a TPU, or with fewer chips than the cell needs, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# the TPU runtime logs inside the checkout, not under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench", ".cache", "tpu_logs"))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report the per-layer metrics")
    return ap.parse_args(argv)


if __name__ == "__main__":
    from bench.lib import harness

    sys.exit(harness.main(parse_args(), T_START))
