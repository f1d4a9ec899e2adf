#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for the program and for the
control, at a cell's own size, over several seeds in one process.

    python3 bench/control.py --workload getput.read_only --seeds 11,12,13 --seconds 16

For each seed this runs the cell as ``bench/run.py`` does (set-up, the
measured window, the check against the reference) and then puts the
control in the program's place: the configuration's reference with the
stated guarantee that the configuration's ``control`` names broken
(``bench/refs/flat.py``: hits that do not refresh LRU recency, or victims
taken most recent first), compared with the reference by the same
numbers.  The program's readings are the lower readings of each limit,
the control's the upper ones.  One JSON line per seed, then a summary line.  The
benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime would otherwise log to a fixed directory outside the
    # checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness

    cell = harness.resolve(args.workload)
    harness.enable_compile_cache()
    program, control = {}, {}
    t0 = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = harness.run_cell(cell, seed, args.seconds, False, t_start=t0,
                                 control=True)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        t0 = time.perf_counter()
        got = {n: c["value"] for n, c in r["checks"].items()}
        for n, v in got.items():
            program.setdefault(n, []).append(v)
        for n, v in r["control"].items():
            control.setdefault(n, []).append(v)
        print(json.dumps({"seed": seed, "correct": r["correct"], "program": got,
                          "control": r["control"], "metrics": r["metrics"]}),
              flush=True)
    print(json.dumps({"workload": cell.name,
                      "lower": {n: max(v) for n, v in program.items()},
                      "upper": {n: min(v) for n, v in control.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
