"""Benchmark runner: one section per paper table/figure family.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only SECTION]

Sections: hit_ratio (Figs 4-13), throughput (Figs 14-26),
synthetic_mix (Figs 27-30), showdown (Fig. 1 analogue: production caches
vs our paths), theorem41 (§4), kernels, serving, robustness (validator /
recovery / degradation ladder, DESIGN.md §13), hierarchy (L1-over-L2
replay, DESIGN.md §14), roofline (reads dryrun_results.json when
present).

The figure sections are thin shims over ``repro.eval`` (DESIGN.md §7) — for
machine-readable, baseline-gated artifacts use
``python -m repro.eval --fig <name> [--quick] [--baseline f.json]``.
"""
import argparse
import json
import os
import sys
import time
import traceback


def _roofline_section():
    path = "dryrun_results.json"
    if not os.path.exists(path):
        print("roofline,skipped,no dryrun_results.json (run repro.launch.dryrun)")
        return
    print("table,config,value")
    with open(path) as f:
        results = json.load(f)
    for key, rec in sorted(results.items()):
        if rec.get("status") != "ok" or "roofline" not in rec:
            continue
        r = rec["roofline"]
        cell = f"{rec['arch']}/{rec['shape']}"
        step = max(r["t_compute"], r["t_memory"], r["t_collective"])
        print(f"roofline,{cell}/bottleneck,{r['bottleneck']}")
        print(f"roofline,{cell}/step_time_s,{step:.4f}")
        print(f"roofline,{cell}/roofline_fraction,{r['roofline_fraction']:.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--backend", default=None,
                    choices=["jnp", "pallas", "ref"],
                    help="restrict the throughput backend sweep to one "
                         "CacheBackend (default: compare all three)")
    ap.add_argument("--shards", type=int, default=4,
                    help="shard count for the sharded-vs-single throughput "
                         "row (power of two)")
    args = ap.parse_args()
    if args.shards < 1 or args.shards & (args.shards - 1):
        ap.error(f"--shards must be a power of two, got {args.shards}")

    from benchmarks import (hit_ratio, kernels_bench, robustness, serving,
                            showdown, synthetic_mix, theorem41, throughput)

    backends = (args.backend,) if args.backend else ("jnp", "pallas", "ref")
    shards = (1, args.shards) if args.shards > 1 else (1,)

    sections = {
        "hit_ratio": lambda: hit_ratio.run(quick=args.quick),
        "throughput": (lambda: throughput.run(
            quick=args.quick, backends=backends, shards=shards)),
        "synthetic_mix": synthetic_mix.run,
        "showdown": lambda: showdown.run(quick=args.quick),
        "theorem41": (lambda: theorem41.run(ks=(8, 64), trials=10))
        if args.quick else theorem41.run,
        "kernels": kernels_bench.run,
        "serving": serving.run,
        "robustness": lambda: robustness.run(quick=args.quick),
        "hierarchy": lambda: throughput.run_hierarchy(quick=args.quick),
        "roofline": _roofline_section,
    }
    failed = []
    for name, fn in sections.items():
        if args.only and name != args.only:
            continue
        print(f"\n### {name} ###", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"### {name} done in {time.time()-t0:.1f}s ###", flush=True)
        except Exception as e:  # noqa: BLE001 — run the other sections, then fail
            traceback.print_exc()
            print(f"### {name} FAILED: {type(e).__name__}: {e} ###", flush=True)
            failed.append(name)
    if failed:
        print(f"### failed sections: {', '.join(failed)} ###", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    sys.exit(main())
