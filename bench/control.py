"""Readings that set a cell's limits: the program, the control and the
faults, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds <s>]
        [--modes fp8,bf16,int4,frozen,half,altered] [--out <file.jsonl>]

For each seed it makes one run of the cell as ``bench/run.py`` does
(set-up, a window at the cell's own load, by default as long as the
benchmark's, the program compared with the reference), then puts the
reference, computed in each of ``--modes``, in the program's place on
the same schedule and data:

- ``fp8``: local training's matmul operands in float8, one step below
  the bfloat16 pass the configuration states (``matmul_precision``);
- ``bf16``: one step below the configuration's float32 storage
  (training, aggregation and float32 payloads in bfloat16);
- ``int4``: the commits rounded to 7 steps instead of 127 (the int8
  lattice's next step down; compressed cells only);

each cell's file names its control among these (``"control"``);
- ``frozen`` / ``half`` / ``altered``: the faults (each apply leaves the
  weights unchanged; each worker trains on half its shard, the mean
  taken over the rest; the first commit of each apply negated).

Each seed prints one JSON line ``{"seed", "program", "<mode>", ...}``
with every number; the benchmark's own runs never run this.  It needs
the chip, as ``bench/run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# the TPU runtime logs inside the checkout, not under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench", ".cache", "tpu_logs"))


def readings(spec, seeds: list[int], seconds: float, modes: list[str]):
    """Yield one dict of readings per seed."""
    from bench.lib import harness, probe

    if spec.traffic["compression"]["commit"] == "none":
        modes = [m for m in modes if m != "int4"]
    log = probe.CompileLog()
    for seed in seeds:
        t0 = time.perf_counter()
        out = harness.run_cell(spec, seed, seconds, False, log, t_start=t0)
        row = {"seed": seed, "correct": out.result["correct"], "metrics": out.result["metrics"],
               "followed": {a: len(s) for a, s in out.replay["schedule"].items()},
               "program": out.numbers}
        row.update(harness.readings(spec, out.replay, None, modes))
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: run_seconds of BENCHMARK.json)")
    ap.add_argument("--modes", default="fp8,bf16,int4,frozen,half,altered")
    ap.add_argument("--out", default=None, help="also append each line to this file")
    args = ap.parse_args(argv)

    from bench.lib import harness
    from bench.lib import spec as spec_mod

    spec = spec_mod.cell_spec(args.workload)
    try:
        harness.device_info(spec.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    harness.configure_cache()
    modes = [m for m in args.modes.split(",") if m]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    seconds = args.seconds or float(spec_mod.benchmark()["run_seconds"])
    for row in readings(spec, seeds, seconds, modes):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
