#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload getput.read_only --seed 7 --seconds 16 --trace 0

One process: it sets up (traffic and warm cache from ``--seed``, every
program of the timed path compiled or read back from the persistent
compilation cache in ``.jax_cache/``), measures for ``--seconds``, checks
what the timed path produced against the configuration's plain reference,
and prints one JSON object.  With ``--trace 0`` its metrics are the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and its metrics are the cell's per-layer ones (the traced window is the
first ``harness.TRACE_SECONDS`` of the steady window).  The numbers
compared for ``correct`` are the last lines on standard error and the
``checks`` key of the result.

Exit codes: 0 with a result; 2 (no result) when JAX finds no TPU or fewer
chips than the cell asks for, or the program is missing from the checkout.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not in this checkout ({ROOT / 'src'})",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime would otherwise log to a fixed directory outside the
    # checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    print(f"bench: {time.perf_counter() - T_START:.3f} s to import JAX and the "
          f"program", file=sys.stderr, flush=True)

    cell = harness.resolve(args.workload)
    harness.enable_compile_cache()
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
