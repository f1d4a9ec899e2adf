"""Serving driver: batched requests through the K-way paged engine.

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
        --requests 16 --policy lru [--tinylfu] [--jitted] [--decode-block 4]

Prints throughput, prefix-cache hit ratio and page-pool stats — the serving
analogue of the paper's §5.3 trace runs.  ``--smoke`` (the default) serves
the architecture's 2-layer smoke config; ``--no-smoke`` serves its
published config with weights drawn from ``--seed`` (e.g. ``--arch
minicpm-2b --no-smoke``: 2.7 B parameters, 5.5 GB in bf16 — a TPU job).
``--jitted`` runs the device-resident one-traced-program serving tick
(DESIGN.md §11) instead of the host loop; ``--decode-block`` sets the
multi-step decode burst both modes schedule.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.core.policies import Policy
from repro.models import lm
from repro.serve.engine import Engine, EngineConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b", choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the 2-layer smoke config (default); "
                         "--no-smoke serves the published config")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--policy", default="lru",
                    choices=[p.name.lower() for p in Policy])
    ap.add_argument("--backend", default="jnp",
                    choices=["jnp", "pallas", "ref"],
                    help="prefix-cache backend (DESIGN.md §3): jnp vector "
                         "ops, the Pallas probe kernel, or the Python oracle")
    ap.add_argument("--tinylfu", action="store_true")
    ap.add_argument("--jitted", action="store_true",
                    help="device-resident serving tick: whole step is ONE "
                         "traced program, one host sync per tick "
                         "(DESIGN.md §11; requires a traceable backend)")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="decode steps per engine tick (both modes run the "
                         "same burst schedule)")
    ap.add_argument("--shared-prefix", type=int, default=48,
                    help="tokens shared by all prompts (prefix-cache fodder)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    spec = configs.get(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    if not (cfg.has_attention and cfg.enc_layers == 0 and not cfg.has_ssm):
        print(f"{args.arch}: paged engine targets decoder-only attention "
              "archs (DESIGN.md §4); serving via plain batched decode only.")
        return 0
    # jitted: the bf16 weights are drawn on the device, never staged in f32
    params = jax.jit(lm.init_params, static_argnums=0)(
        cfg, jax.random.key(args.seed))
    eng = Engine(cfg, params, EngineConfig(
        page=8, num_sets=32, ways=8, policy=Policy[args.policy.upper()],
        tinylfu=args.tinylfu, max_batch=8, max_seq=256, private_pages=256,
        backend=args.backend, jitted=args.jitted,
        decode_block=args.decode_block,
    ))
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(2, cfg.vocab_size - 1, args.shared_prefix)
    t0 = time.time()
    for _ in range(args.requests):
        tail = rng.integers(2, cfg.vocab_size - 1, rng.integers(4, 16))
        eng.submit(np.concatenate([shared, tail]), max_new=args.max_new)
    fin = eng.run()
    dt = time.time() - t0
    total_new = sum(len(r.generated) for r in fin.values())
    print(f"served {len(fin)} requests, {total_new} tokens "
          f"in {dt:.1f}s ({total_new/dt:.1f} tok/s)")
    print(f"prefix-cache hit ratio: {eng.hit_ratio():.3f}  stats: {eng.stats}")
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    sys.exit(main())
