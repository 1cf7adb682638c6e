"""Readings of a cell's compared numbers over many seeds, for setting
its limits: the program as the configuration states it, and the
lower-precision control (every layer on the program's own w4a4 path,
the nearest precision below the configuration's int8).

    python3 bench/control.py --workload <name> --seconds 3 \\
        --seeds 11 12 13 [--low-bits 4]

Runs every seed in this one process on the chip (set-up is paid once
for the compiled programs) and prints one JSON line per seed with the
checks.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import cell  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--low-bits", type=int, default=None)
    args = ap.parse_args(argv)
    spec = cell.load_spec(args.workload)
    device, count, peaks = cell.require_device(spec.chips)
    for seed in args.seeds:
        t = time.perf_counter()
        res = cell.run(spec, seed, args.seconds, False, device=device,
                       count=count, peaks=peaks, low_bits=args.low_bits,
                       t_start=t)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "low_bits": args.low_bits,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"],
                          "run_s": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
