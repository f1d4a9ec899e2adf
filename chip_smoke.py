#!/usr/bin/env python3
"""On-chip smoke test: the k-way cache, its Pallas kernels and the paged
serving engine on a TPU, at deployment sizes, each checked against the
repo's own reference for it.

    python3 chip_smoke.py [--seed N]     # phases 1-3, one chip
    python3 chip_smoke.py --chips 4      # phase 4 only, a 4-chip host

Phases (all data generated from ``--seed``):

  1. cache-hbm   jnp chunked-scan replay of 2^24 zipf(0.99) requests over a
                 2^26-key catalog into a 2^21-set x 8-way LRU cache (320 MiB
                 of state lanes in HBM); per-chunk hit/eviction counts equal
                 the same replay on the host CPU backend over a prefix, and
                 the final state passes ``robust.invariants.check_cache``.
  2. kernels     Mosaic-compiled Pallas kernels, each bit-identical to its
                 jnp twin on the chip: the flat resident megakernel at 2048
                 sets x 8, the hierarchy kernel (L1 64x16 over a 2^18-set L2
                 in HBM) and the fused-probe ``access``; no degradation
                 event may be recorded.
  3. serving     the jitted paged ``Engine`` serving minicpm-2b at its
                 published widths (2.7 B parameters, bf16, weights from the
                 seed) with the prefix cache on the pallas backend and
                 ``paged_attention`` compiled; prefix-cache counts, cache
                 state and tokens equal the host-loop engine's on the chip.
  4. sharded     (``--chips 4`` only) ``ShardedCache`` over a 4-device
                 ``sets`` mesh holding 2^26 entries, state and trace placed
                 by ``NamedSharding``, against the one-device vmap path.

Each phase prints one line: what ran, at what size, the path taken, and its
check.  The last line is the JSON verdict.  The script exits non-zero, and
prints no verdict, when JAX finds no TPU.  It is one process and starts no
other.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class CompileClock:
    """Sums XLA compile time and persistent-cache hits, as JAX reports
    them through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _np(x):
    import numpy as np
    return np.asarray(x)


def _same(a, b) -> bool:
    import numpy as np
    return np.array_equal(_np(a), _np(b))


def _same_state(a, b) -> bool:
    """Every lane and the clock of two ``KWayState``s are equal."""
    return all(_same(getattr(a, f), getattr(b, f))
               for f in ("keys", "fprint", "vals", "meta_a", "meta_b",
                         "clock"))


def _events_since(c0) -> list:
    from repro.robust import events
    return [f"{e.component}:{e.reason}" for e in events.since(c0)]


def _zipf_chunks(n, catalog, batch, seed, alpha=0.99):
    from repro.core import router, traces
    tr = traces.generate("zipf", n, seed=seed, catalog=catalog, alpha=alpha)
    return router.pad_chunks(tr, batch)


# ---------------------------------------------------------------------------
# phase 1: the cache at deployment size, state in HBM
# ---------------------------------------------------------------------------

def phase_cache_hbm(seed, *, sets=1 << 21, ways=8, requests=1 << 24,
                    catalog=1 << 26, batch=4096, check_chunks=64):
    import jax
    from repro.core.backend import make_backend
    from repro.core.kway import KWayConfig
    from repro.core.policies import Policy
    from repro.robust.invariants import check_cache, explain_cache

    cfg = KWayConfig(num_sets=sets, ways=ways, policy=Policy.LRU)
    t0 = time.perf_counter()
    chunks, en = _zipf_chunks(requests, catalog, batch, seed)
    t_gen = time.perf_counter() - t0

    be = make_backend("jnp", cfg)
    t0 = time.perf_counter()
    hits, evs, st, _ = be.replay(be.init(), chunks, en)
    hits, evs = _np(hits), _np(evs)
    t_run = time.perf_counter() - t0

    rep = check_cache(cfg, st, vals_mode="key")
    clean = rep.clean()

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        cb = make_backend("jnp", cfg)
        h_cpu, e_cpu, _, _ = cb.replay(cb.init(), chunks[:check_chunks],
                                       en[:check_chunks])
    same = (_same(hits[:check_chunks], h_cpu)
            and _same(evs[:check_chunks], e_cpu))
    ok = same and clean
    lane_mib = 5 * sets * ways * 4 / 2**20
    why = "" if clean else "; ".join(explain_cache(rep, limit=4))
    return ok, (
        f"jnp chunked-scan replay, {sets} sets x {ways} ways LRU "
        f"({sets * ways} entries, {lane_mib:.0f} MiB of lanes in HBM), "
        f"zipf a=0.99 {requests} req over {catalog} keys, B={batch}, "
        f"{len(chunks)} chunks: hit ratio {hits.sum() / requests:.4f}, "
        f"{evs.sum()} evictions, trace {t_gen:.1f} s, replay {t_run:.1f} s "
        f"(incl. compile); per-chunk hits/evictions == host CPU on the first "
        f"{check_chunks} chunks: {same}; check_cache clean: {clean}{why}")


# ---------------------------------------------------------------------------
# phase 2: the Pallas kernels, compiled, against their jnp twins
# ---------------------------------------------------------------------------

def phase_kernels(seed, *, flat_sets=2048, ways=8, flat_requests=1 << 16,
                  flat_batch=512, l1_sets=64, l1_ways=16, l2_sets=1 << 18,
                  hier_requests=1 << 14, hier_batch=256, access_batches=8,
                  access_batch=1024):
    import jax.numpy as jnp
    from repro.core import hierarchy as H
    from repro.core.admission import for_capacity
    from repro.core.backend import make_backend
    from repro.core.kway import KWayConfig
    from repro.core.policies import Policy
    from repro.kernels import interpret
    from repro.kernels import replay as kreplay
    from repro.robust import events

    c0 = events.cursor()
    parts, ok = [], not interpret()

    # (a) flat resident megakernel, plain LRU and LFU + TinyLFU admission
    for policy, tl_on in ((Policy.LRU, False), (Policy.LFU, True)):
        cfg = KWayConfig(num_sets=flat_sets, ways=ways, policy=policy)
        tl = for_capacity(cfg.capacity) if tl_on else None
        chunks, en = _zipf_chunks(flat_requests, 8 * cfg.capacity,
                                  flat_batch, seed + 1)
        pb, jb = make_backend("pallas", cfg), make_backend("jnp", cfg)
        fits = pb.resident_fits()
        n0 = sum(v for k, v in kreplay.trace_counts().items()
                 if k[0] == "launch")
        t0 = time.perf_counter()
        h1, e1, s1, _ = pb.replay(pb.init(), chunks, en, tinylfu=tl)
        h1 = _np(h1)
        t_k = time.perf_counter() - t0
        launches = sum(v for k, v in kreplay.trace_counts().items()
                       if k[0] == "launch") - n0
        h2, e2, s2, _ = jb.replay(jb.init(), chunks, en, tinylfu=tl)
        same = (_same(h1, h2) and _same(e1, e2) and _same_state(s1, s2))
        ok &= fits and launches == 1 and same
        name = f"{policy.name}{'+tinylfu' if tl_on else ''}"
        parts.append(
            f"resident {name} {flat_sets}x{ways} {len(chunks)}x{flat_batch}: "
            f"1 launch {launches == 1}, {t_k:.2f} s, hit ratio "
            f"{h1.sum() / flat_requests:.4f}, == jnp scan {same}")

    # (b) hierarchy kernel: L1 in VMEM over an L2 in HBM
    cfg = KWayConfig(num_sets=l2_sets, ways=ways, policy=Policy.LRU)
    hier = H.HierarchyConfig(l1_sets=l1_sets, l1_ways=l1_ways)
    chunks, en = _zipf_chunks(hier_requests, 4 * cfg.capacity, hier_batch,
                              seed + 2)
    pb = make_backend("pallas", cfg)
    t0 = time.perf_counter()
    h1, e1, hs1, _ = pb.replay(pb.init(), chunks, en, hierarchy=hier)
    h1 = _np(h1)
    t_k = time.perf_counter() - t0
    h2, e2, hs2, _ = H.replay_l1_over_l2(
        cfg, hier, H.make_hier(cfg, hier), jnp.asarray(chunks),
        jnp.asarray(en))
    same = (_same(h1, h2) and _same(e1, e2) and _same_state(hs1.l1, hs2.l1)
            and _same_state(hs1.l2, hs2.l2))
    ok &= same
    l2_gib = l2_sets * H.ROW_W * 4 / 2**30
    parts.append(
        f"hierarchy L1 {l1_sets}x{l1_ways} over L2 {l2_sets}x{ways} "
        f"({l2_gib:.2f} GiB packed rows) {len(chunks)}x{hier_batch}: "
        f"{t_k:.2f} s, hit ratio {h1.sum() / hier_requests:.4f}, "
        f"== jnp twin {same}")

    # (c) fused-probe access, batch by batch
    cfg = KWayConfig(num_sets=flat_sets, ways=ways, policy=Policy.LRU)
    chunks, _ = _zipf_chunks(access_batches * access_batch,
                             4 * cfg.capacity, access_batch, seed + 3)
    pb, jb = make_backend("pallas", cfg), make_backend("jnp", cfg)
    sp, sj = pb.init(), jb.init()
    same, hits = True, 0
    for keys in chunks:
        vals = jnp.asarray(keys.astype("int32"))
        sp, hp, vp, kp, ep = pb.access(sp, keys, vals)
        sj, hj, vj, kj, ej = jb.access(sj, keys, vals)
        same &= all(_same(a, b) for a, b in
                    ((hp, hj), (vp, vj), (kp, kj), (ep, ej)))
        hits += int(_np(hp).sum())
    same &= _same_state(sp, sj)
    ok &= same
    parts.append(
        f"fused-probe access {access_batches}x{access_batch} on "
        f"{flat_sets}x{ways}: hits {hits}, == jnp access {same}")

    demoted = _events_since(c0)
    ok &= not demoted
    return ok, (
        f"{'interpreted' if interpret() else 'Mosaic-compiled'}; "
        + "; ".join(parts)
        + f"; degradation events: {demoted or 'none'}")


# ---------------------------------------------------------------------------
# phase 3: the paged serving engine at published widths
# ---------------------------------------------------------------------------

def phase_serving(seed, *, arch="minicpm-2b", smoke=False, requests=8,
                  shared=64, max_new=8, page=16, num_sets=16, ways=8,
                  private_pages=128, max_seq=256, max_prompt=96):
    import jax
    import numpy as np
    from repro import configs
    from repro.core.policies import Policy
    from repro.kernels import interpret
    from repro.models import lm
    from repro.serve.engine import Engine, EngineConfig

    spec = configs.get(arch)
    cfg = spec.smoke if smoke else spec.config
    params = jax.jit(lm.init_params, static_argnums=0)(
        cfg, jax.random.key(seed))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))

    rng = np.random.default_rng(seed)
    prefix = rng.integers(2, cfg.vocab_size - 1, shared)
    prompts = [np.concatenate([prefix, rng.integers(
        2, cfg.vocab_size - 1, rng.integers(4, 17))])
        for _ in range(requests)]

    def serve(jitted):
        eng = Engine(cfg, params, EngineConfig(
            page=page, num_sets=num_sets, ways=ways, policy=Policy.LRU,
            max_batch=requests, max_seq=max_seq, max_prompt=max_prompt,
            private_pages=private_pages, backend="pallas", jitted=jitted))
        for p in prompts:
            eng.submit(p, max_new=max_new)
        t0 = time.perf_counter()
        fin = eng.run()
        dt = time.perf_counter() - t0
        toks = {rid: list(r.generated) for rid, r in fin.items()}
        kstate = eng._sstate.kstate if jitted else eng.kstate
        return eng.stats, toks, kstate, dt

    st_j, tok_j, ks_j, t_j = serve(jitted=True)
    st_h, tok_h, ks_h, t_h = serve(jitted=False)
    keys = ("prefix_hits", "prefix_lookups", "evictions", "prefills",
            "decode_steps", "degradation_events")
    counts_same = all(st_j[k] == st_h[k] for k in keys)
    pages_same = _same(ks_j.keys, ks_h.keys) and _same(ks_j.vals, ks_h.vals)
    resident = int((_np(ks_j.keys) != 0xFFFFFFFF).sum())
    tokens_same = tok_j == tok_h
    # attend_paged runs the kernel exactly when it is not interpreted
    compiled = not interpret()
    ok = (compiled and counts_same and pages_same and tokens_same
          and st_j["degradation_events"] == 0)
    n_tok = sum(len(t) for t in tok_j.values())
    return ok, (
        f"{cfg.name} {cfg.num_layers}L d={cfg.d_model} ({n_params / 1e9:.2f} "
        f"B params, {n_bytes / 1e9:.2f} GB), jitted Engine + pallas prefix "
        f"cache {num_sets}x{ways} pages of {page}, paged_attention "
        f"{'compiled' if compiled else 'reference (interpret platform)'}, "
        f"{requests} requests sharing {shared} tokens: "
        f"{n_tok} tokens, jitted {t_j:.1f} s, host loop {t_h:.1f} s (incl. "
        f"compile); prefix hits {st_j['prefix_hits']}/"
        f"{st_j['prefix_lookups']}, evictions {st_j['evictions']}, "
        f"{resident} shared pages resident; counts == host loop "
        f"{counts_same}, cache pages == host loop {pages_same}, tokens == "
        f"host loop {tokens_same}")


# ---------------------------------------------------------------------------
# phase 4 (--chips 4): the sharded cache over a 4-device mesh
# ---------------------------------------------------------------------------

def phase_sharded(seed, *, devices=4, sets=1 << 23, ways=8, requests=1 << 22,
                  catalog=1 << 26, batch=4096, access_chunks=16):
    import jax
    import jax.numpy as jnp
    from repro.core import traces
    from repro.core.kway import KWayConfig
    from repro.core.policies import Policy
    from repro.core.sharded import ShardedCache, ShardedConfig

    cfg = ShardedConfig(cache=KWayConfig(num_sets=sets, ways=ways,
                                         policy=Policy.LRU),
                        num_shards=devices)
    mesh = jax.make_mesh((devices,), ("sets",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    tr = traces.generate("zipf", requests, seed=seed, catalog=catalog,
                         alpha=0.99)
    on_mesh, on_one = ShardedCache(cfg, mesh=mesh), ShardedCache(cfg)

    t0 = time.perf_counter()
    h_m, d_m, st_m = on_mesh.replay(tr, batch)
    jax.block_until_ready(st_m)
    t_m = time.perf_counter() - t0
    placed = st_m.keys.sharding.spec[0] == "sets"
    t0 = time.perf_counter()
    h_1, d_1, st_1 = on_one.replay(tr, batch)
    jax.block_until_ready(st_1)
    t_1 = time.perf_counter() - t0
    state_same = all(_same(getattr(st_m, f), getattr(st_1, f))
                     for f in ("keys", "vals", "meta_a", "meta_b", "clock"))

    # per-chunk counts through the batched access entry point
    chunk_same = True
    sm, s1 = on_mesh.init(), on_one.init()
    for i in range(access_chunks):
        keys = tr[i * batch:(i + 1) * batch]
        vals = jnp.asarray(keys.astype("int32"))
        sm, hm, _, _, em = on_mesh.access(sm, keys, vals)
        s1, h1, _, _, e1 = on_one.access(s1, keys, vals)
        chunk_same &= (int(_np(hm).sum()) == int(_np(h1).sum())
                       and int(_np(em).sum()) == int(_np(e1).sum()))
    ok = placed and state_same and chunk_same and (h_m, d_m) == (h_1, d_1)
    return ok, (
        f"ShardedCache {devices} shards over a 'sets' mesh of "
        f"{devices} devices, {sets * ways} entries ({sets // devices} sets x "
        f"{ways} per device), zipf a=0.99 {requests} req, B={batch}: hits "
        f"{h_m}, deferred {d_m}, mesh {t_m:.1f} s, one-device vmap "
        f"{t_1:.1f} s (incl. compile); state sharded on 'sets' {placed}; "
        f"totals == vmap {(h_m, d_m) == (h_1, d_1)}, final state == vmap "
        f"{state_same}, per-chunk hits/evictions == vmap on {access_chunks} "
        f"chunks {chunk_same}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, over four chips")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    clock = CompileClock()

    phases = ([("sharded", phase_sharded)] if args.chips == 4 else
              [("cache-hbm", phase_cache_hbm), ("kernels", phase_kernels),
               ("serving", phase_serving)])
    failed = []
    for i, (name, fn) in enumerate(phases, 1):
        c0, n0 = clock.seconds, clock.cache_hits
        t0 = time.perf_counter()
        try:
            ok, line = fn(args.seed)
        except Exception as e:  # noqa: BLE001 — report the phase, run the rest
            traceback.print_exc()
            ok, line = False, f"raised {type(e).__name__}: {e}"
        print(f"phase {i} {name}: {'PASS' if ok else 'FAIL'} — {line} "
              f"[{time.perf_counter() - t0:.1f} s, compile "
              f"{clock.seconds - c0:.1f} s, "
              f"{clock.cache_hits - n0} compile-cache hits]", flush=True)
        if not ok:
            failed.append(name)

    print(f"compile cache {cache_dir}: {clock.seconds:.1f} s compiling, "
          f"{clock.cache_hits} programs read back", flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
