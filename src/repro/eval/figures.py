"""Figure-by-figure reproduction entry points (paper Figs. 4-30).

Each function runs one figure family's sweep and returns
``(spec_dict, records, skipped)`` ready for ``artifacts.make_artifact``:

  * ``hit_ratio_vs_associativity`` — Figs. 4-13: hit ratio of k ∈ {4,8,32},
    sampled-8 and fully-associative caches per trace family × policy.
  * ``sampled_vs_limited``         — the Redis-style sampled-k full cache vs
    the paper's limited-associativity k-way cache at matched k.
  * ``admission_ablation``         — TinyLFU on/off at k=8 (paper §5.2).
  * ``throughput_vs_batch``        — Figs. 14-26 analogue: batch size stands
    in for thread count; layouts, backends and the sharded layer.
  * ``throughput_vs_shards``       — the threads-vs-throughput scaling plot:
    shards stand in for threads, each bringing its own per-tick request
    stream; includes the single-scan no-host-sync replay rows.
  * ``showdown``                   — Fig. 1 analogue: req/s vs thread count
    for production caches (cachetools + global lock, lock-striped k-way)
    next to our batched/resident device paths, with gateable hit-ratio
    parity records.
  * ``synthetic_mix``              — Figs. 27-30: fixed hit-rate workloads.
  * ``serving``                    — end-to-end prefix-cache serving rows.
  * ``serving_engine``             — host-loop vs device-resident jitted
    serving tick: req/s + tok/s percentiles and token/hit-ratio parity.

Hit-ratio figures run on the stacked sweep runner (one compile per cache
shape); throughput figures are wall-clock timed per configuration and are
marked non-comparable in artifacts (timings do not gate baselines).
"""
from __future__ import annotations

import numpy as np

from repro.core.policies import Policy
from repro.eval import runner
from repro.eval.runner import HitRatioSpec
from repro.eval.timing import (time_chained_percentiles, time_host,
                               time_jitted, time_jitted_percentiles,
                               time_replay_percentiles)

QUICK_N = 6_000
FULL_N = 60_000


def _run(spec: HitRatioSpec, progress=None):
    records, skipped = runner.run_hit_ratio_sweep(spec, progress=progress)
    return spec.to_dict(), records, skipped


def hit_ratio_vs_associativity(quick: bool = False, progress=None,
                               backends=("jnp", "pallas")):
    """Paper Figs. 4-13: the k=8 line sits on the fully-associative line."""
    spec = HitRatioSpec(
        families=("zipf", "zipf_shift", "scan_loop", "oltp_mix")
        if quick else ("zipf", "zipf_shift", "scan_loop", "oltp_mix",
                       "recency"),
        policies=(Policy.LRU, Policy.LFU, Policy.HYPERBOLIC),
        assoc=("k4", "k8", "k32", "sampled8", "full"),
        backends=tuple(backends),
        capacity=1024,
        n=QUICK_N if quick else FULL_N,
        seeds=(42,) if quick else (42, 43, 44),
    )
    return _run(spec, progress)


def sampled_vs_limited(quick: bool = False, progress=None):
    """Sampled-k full-associativity (Redis style) vs limited-associativity
    k-way at matched k — the paper's 'sampling is the wrong shortcut' plot."""
    spec = HitRatioSpec(
        families=("zipf", "scan_loop", "oltp_mix", "recency"),
        policies=(Policy.LRU, Policy.LFU),
        assoc=("k4", "sampled4", "k8", "sampled8", "k16", "sampled16",
               "full"),
        backends=("jnp",),
        capacity=1024,
        n=QUICK_N if quick else FULL_N,
        seeds=(42,) if quick else (42, 43, 44),
    )
    return _run(spec, progress)


def admission_ablation(quick: bool = False, progress=None,
                       admissions=("none", "tinylfu")):
    """TinyLFU admission on/off at k=8 (the paper pairs it with LFU)."""
    spec = HitRatioSpec(
        families=("zipf", "zipf_shift", "scan_loop", "oltp_mix"),
        policies=(Policy.LRU, Policy.LFU, Policy.HYPERBOLIC),
        assoc=("k8",),
        backends=("jnp",),
        admissions=tuple(admissions),
        capacity=1024,
        n=QUICK_N if quick else FULL_N,
        seeds=(42,) if quick else (42, 43, 44),
    )
    return _run(spec, progress)


# ---------------------------------------------------------------------------
# throughput figures (wall-clock; non-comparable in artifacts)
# ---------------------------------------------------------------------------

THROUGHPUT_CAPACITY = 4096


def _throughput_impls(policy):
    from repro.core.kway import KWayConfig, fully_associative
    return {
        "kway-soa": KWayConfig(num_sets=THROUGHPUT_CAPACITY // 8, ways=8,
                               policy=policy, layout="soa"),
        "kway-aos": KWayConfig(num_sets=THROUGHPUT_CAPACITY // 8, ways=8,
                               policy=policy, layout="aos"),
        "sampled": KWayConfig(num_sets=THROUGHPUT_CAPACITY // 128, ways=128,
                              policy=policy, sample=8),
        "full": fully_associative(THROUGHPUT_CAPACITY, policy),
    }


def _tp_record(name: str, batch: int, mops: float, **extra) -> dict:
    rec = {"id": f"{name}/batch{batch}", "impl": name, "batch": batch,
           "metric": "mops_per_s", "value": round(mops, 3),
           "comparable": False}
    rec.update(extra)
    return rec


def _replay_hit_ratio(be, trace, batch, tinylfu=None, scan=False):
    """Hit ratio of one whole-trace ``be.replay``: on pallas the path it
    picks from the VMEM fit, the megakernel when the state fits.
    ``scan=True`` runs ``be.replay_scan``, the chunked scan and the
    megakernel's oracle (on jnp the same program as ``replay``)."""
    from repro.core import router
    chunks, enabled = router.pad_chunks(trace, batch)
    replay = be.replay_scan if scan else be.replay
    hits, _, _, _ = replay(be.init(), chunks, enabled, tinylfu=tinylfu)
    return float(np.asarray(hits).sum()) / len(trace)


def throughput_vs_batch(quick: bool = False, progress=None,
                        backends=("jnp", "pallas", "ref"), shards=(1, 4)):
    """Paper Figs. 14-26 analogue: ops/sec vs batch size (thread analogue)
    across layouts, the CacheBackend substrates, and the sharded layer."""
    import jax
    import jax.numpy as jnp
    from repro.core import kway, traces
    from repro.core.backend import make_backend
    from repro.core.sharded import ShardedCache, ShardedConfig

    batches = (64, 256) if quick else (64, 256, 1024)
    policy = Policy.LRU
    n_warm = 20_480
    tr = traces.generate("zipf", n_warm + 4096, seed=7, catalog=1 << 14)
    records = []

    def warm(cfg):
        state = kway.make_cache(cfg)
        for chunk in jnp.asarray(tr[:n_warm].reshape(-1, 512)):
            state, *_ = kway.access(cfg, state, chunk,
                                    chunk.astype(jnp.int32))
        return state

    soa_state = None
    for name, cfg in _throughput_impls(policy).items():
        if progress:
            progress(f"throughput impl {name}")
        state = warm(cfg)
        if name == "kway-soa":
            soa_state = state
        for b in batches:
            keys = jnp.asarray(tr[n_warm:n_warm + b])
            vals = keys.astype(jnp.int32)
            fn = jax.jit(lambda s, k, v: kway.access(cfg, s, k, v)[0])
            dt = time_jitted(fn, state, keys, vals)
            records.append(_tp_record(name, b, b / dt / 1e6))

    # unified backend layer: fused single-probe access vs the two-phase
    # get-then-put oracle, per backend, p50/p90 steady-state per repetition
    cfg = _throughput_impls(policy)["kway-soa"]
    state = soa_state if soa_state is not None else warm(cfg)
    for bname in backends:
        if progress:
            progress(f"throughput backend {bname}")
        be = make_backend(bname, cfg)
        # interpret-mode pallas compiles slowly at large B; the ref oracle is
        # sequential Python — keep their batches proportionate.
        bl = {"jnp": batches, "pallas": tuple(b for b in batches if b <= 256),
              "ref": (64,)}.get(bname, batches)
        for b in bl:
            keys = jnp.asarray(tr[n_warm:n_warm + b])
            vals = keys.astype(jnp.int32)
            if bname == "ref":
                # the sequential oracle has no fused path; one two-phase row
                dt = time_host(be.access, state, keys, vals)
                records.append(_tp_record("backend-ref-twophase", b,
                                          b / dt / 1e6))
                continue
            p50 = {}
            for vname, acc in (("fused", be.access),
                               ("twophase", be.access_two_phase)):
                fn = jax.jit(lambda s, k, v, _a=acc: _a(s, k, v)[0])
                st = time_jitted_percentiles(fn, state, keys, vals)
                p50[vname] = st["p50"]
                records.append(_tp_record(
                    f"backend-{bname}-{vname}", b, b / st["p50"] / 1e6,
                    p90_mops=round(b / st["p90"] / 1e6, 3),
                    p50_req_s=round(b / st["p50"], 1),
                    p90_req_s=round(b / st["p90"], 1)))
            records.append(_tp_record(
                f"backend-{bname}-fused-speedup", b,
                p50["twophase"] / p50["fused"], metric="speedup_x"))
        if bname == "jnp":
            # buffer-donating fused path: the state is consumed and rebound
            # every step (KWayState updated in place), so the timing loop
            # chains it instead of re-passing one donated buffer
            for b in bl:
                keys = jnp.asarray(tr[n_warm:n_warm + b])
                vals = keys.astype(jnp.int32)
                st_d = jax.tree_util.tree_map(lambda x: x.copy(), state)

                def step_d():
                    nonlocal st_d
                    st_d, *_ = kway.access_donated(cfg, st_d, keys, vals)
                    return st_d

                st = time_chained_percentiles(step_d)
                records.append(_tp_record(
                    "backend-jnp-fused-donated", b, b / st["p50"] / 1e6,
                    p90_mops=round(b / st["p90"] / 1e6, 3),
                    p50_req_s=round(b / st["p50"], 1),
                    p90_req_s=round(b / st["p90"], 1)))

    # set-sharded execution: 1 shard vs N shards (fused access, donated
    # shard-state leaves — every chunk rebinds the returned state)
    b = max(batches)
    for ns in shards:
        if progress:
            progress(f"throughput sharded x{ns}")
        sc = ShardedCache(ShardedConfig(cache=cfg, num_shards=ns,
                                        donate=True))
        st = sc.init()
        chunk0 = np.asarray(tr[:b], np.uint32)
        for _ in range(3):  # warm the jit caches + shard states
            st, *_ = sc.access(st, chunk0, chunk0.astype(np.int32))

        def run_chunks(n_chunks):
            nonlocal st
            for i in range(n_chunks):
                off = n_warm + (i * b) % 4096
                chunk = np.asarray(tr[off:off + b], np.uint32)
                if len(chunk) < b:
                    chunk = chunk0
                st, *_ = sc.access(st, chunk, chunk.astype(np.int32))
            # access() returns device arrays now (the router runs on
            # device); block so the timed region covers the execution,
            # not just the async dispatch
            jax.block_until_ready(st.keys)

        n_chunks = 10
        dt = time_host(run_chunks, n_chunks, iters=1) / n_chunks
        records.append(_tp_record(f"sharded-{ns}shard", b, b / dt / 1e6))

    # trace-resident replay megakernel vs the chunked-scan replay on the
    # kernel path (headline rows; the full sweep + bit-identity gate live
    # in throughput_resident / benchmarks.throughput --resident-compare)
    if "pallas" in backends:
        from repro.core.backend import make_backend
        n_rep, b_rep = 16_384, 256
        tr_rep = tr[:n_rep]
        pb = make_backend("pallas", cfg)
        rp50 = {}
        for mode, scan in (("scan", True), ("resident", False)):
            if progress:
                progress(f"replay {mode} pallas")
            st = time_replay_percentiles(
                lambda _s=scan: _replay_hit_ratio(pb, tr_rep, b_rep,
                                                  scan=_s),
                iters=3)
            rp50[mode] = st["p50"]
            records.append(_tp_record(
                f"replay-{mode}-pallas", b_rep, n_rep / st["p50"] / 1e6,
                n=n_rep, p50_req_s=round(n_rep / st["p50"], 1),
                p90_req_s=round(n_rep / st["p90"], 1),
                reps_discarded=st["reps_discarded"]))
        records.append(_tp_record(
            "replay-resident-speedup-pallas", b_rep,
            rp50["scan"] / rp50["resident"], metric="speedup_x"))

    spec = {"quick": quick, "batches": list(batches),
            "policy": policy.name, "backends": list(backends),
            "shards": list(shards), "capacity": THROUGHPUT_CAPACITY}
    return spec, records, []


def throughput_resident(quick: bool = False, progress=None,
                        backends=("jnp", "pallas")):
    """Trace-resident replay megakernel vs the chunked-scan replay
    (DESIGN.md §10): whole-trace replay req/s, p50/p90 steady-state.

    Rows per backend:

      * ``replay-scan-{b}``     — the chunked ``lax.scan`` replay (one
        jitted scan; on pallas, one kernel launch + scatter pass per chunk);
      * ``replay-resident-{b}`` — ``CacheBackend.replay``: on pallas the
        megakernel (ONE launch for the whole trace, state lanes pinned in
        VMEM, zero HBM state round-trips), on jnp the scanned default (the
        comparison anchor);
      * ``replay-resident-speedup-{b}`` — resident p50 over scan p50.

    Plus comparable ``resident-eq/...`` hit-ratio records over a small
    (family × policy × ±TinyLFU) grid: ``value`` is the resident hit ratio
    and ``scan_value`` the chunked-scan one — the two must be EXACTLY equal
    (tol 0.0; the megakernel is bit-identical by construction), which is
    what the CI ``--resident-compare`` gate enforces.
    """
    from repro.core import admission, traces
    from repro.core.backend import make_backend
    from repro.core.kway import KWayConfig

    policy = Policy.LRU
    batch = 256
    n = 16_384 if quick else 65_536
    kcfg = KWayConfig(num_sets=THROUGHPUT_CAPACITY // 8, ways=8,
                      policy=policy)
    tr = traces.generate("zipf", n, seed=7, catalog=1 << 14)
    records = []
    p50 = {}
    for bname in backends:
        be = make_backend(bname, kcfg)
        for mode, scan in (("scan", True), ("resident", False)):
            if progress:
                progress(f"replay {mode} {bname}")
            st = time_replay_percentiles(
                lambda _b=be, _s=scan: _replay_hit_ratio(_b, tr, batch,
                                                         scan=_s),
                iters=3 if quick else 5)
            p50[(bname, mode)] = st["p50"]
            records.append(_tp_record(
                f"replay-{mode}-{bname}", batch, n / st["p50"] / 1e6,
                n=n, mode=mode, backend=bname,
                p50_req_s=round(n / st["p50"], 1),
                p90_req_s=round(n / st["p90"], 1),
                reps_discarded=st["reps_discarded"]))
        records.append(_tp_record(
            f"replay-resident-speedup-{bname}", batch,
            p50[(bname, "scan")] / p50[(bname, "resident")],
            metric="speedup_x", backend=bname))

    # bit-identity records: resident (pallas megakernel) vs chunked scan
    n_eq = QUICK_N if quick else FULL_N
    eq_backend = "pallas" if "pallas" in backends else backends[0]
    tlfu = admission.for_capacity(1024)
    for family in ("zipf", "scan_loop"):
        tre = traces.generate(family, n_eq, seed=42)
        for pol in (Policy.LRU, Policy.LFU):
            for adm in ("none", "tinylfu"):
                if progress:
                    progress(f"resident-eq {family}/{pol.name}/{adm}")
                cfg = KWayConfig(num_sets=128, ways=8, policy=pol)
                be = make_backend(eq_backend, cfg)
                tl = tlfu if adm == "tinylfu" else None
                hr_res = _replay_hit_ratio(be, tre, batch, tinylfu=tl)
                hr_scan = _replay_hit_ratio(be, tre, batch, tinylfu=tl,
                                            scan=True)
                records.append({
                    "id": f"resident-eq/{family}/{pol.name}/{adm}",
                    "family": family, "policy": pol.name,
                    "admission": adm, "backend": eq_backend,
                    "batch": batch, "n": n_eq, "capacity": 1024,
                    "metric": "hit_ratio", "value": hr_res,
                    "scan_value": hr_scan,
                    "comparable": True, "tol": 0.0,
                })
    spec = {"quick": quick, "backends": list(backends), "batch": batch,
            "n": n, "n_eq": n_eq, "policy": policy.name,
            "capacity": THROUGHPUT_CAPACITY}
    return spec, records, []


def throughput_vs_shards(quick: bool = False, progress=None,
                         shards=(1, 2, 4, 8)):
    """The paper's threads-vs-throughput plot (Figs. 14-26 headline), with
    set shards standing in for threads: each shard is one consumer bringing
    its own fixed-size request stream per serving tick, so the offered load
    per tick is ``D × tick_batch`` — exactly the paper's methodology, where
    every added thread drives its own request loop.

    Rows per shard count (jnp backend, LRU, k=8):

      * ``sharded-jnp-shard{D}`` — p50/p90 req/s of the routed serving tick
        (ONE jitted call: device router + per-shard fused access + unscatter,
        shard states donated and rebound).  The scaling headline: per-tick
        dispatch cost is flat while the routed tick carries D× requests.
      * ``scan-shard{D}``        — whole-trace replay as a single jitted
        ``lax.scan`` (``ShardedCache.replay``): ONE host sync for the entire
        trace, no per-chunk bucketing or transfers (the no-host-sync row).
      * ``scaling-shard{D}``     — tick p50 speedup over shard1.

    Plus comparable hit-ratio records for shards ∈ {1, 4} on a slice of the
    baseline grid (tol-gated against benchmarks/baselines/quick.json by the
    CI perf-smoke step — batched replay tracks the B=1 baseline within a
    small band, it is not bit-equal).
    """
    import numpy as np

    from repro.core import traces
    from repro.core.kway import KWayConfig
    from repro.core.sharded import ShardedCache, ShardedConfig
    from repro.eval.runner import SweepPoint, replay_sharded_point

    policy = Policy.LRU
    kcfg = KWayConfig(num_sets=THROUGHPUT_CAPACITY // 8, ways=8,
                      policy=policy)
    tick_batch = 32                      # per-shard per-tick lane budget
    n_scan = 65_536 if quick else 262_144
    tr = traces.generate("zipf", n_scan, seed=7, catalog=1 << 14)
    records = []
    tick_p50 = {}

    for d in shards:
        if progress:
            progress(f"shards={d} (tick + scan)")
        bg = d * tick_batch
        sc = ShardedCache(ShardedConfig(cache=kcfg, num_shards=d,
                                        donate=True))
        st = sc.init()
        offs = [(i * bg) % (n_scan - bg) for i in range(64)]
        it = {"i": 0}

        def tick():
            chunk = tr[offs[it["i"] % len(offs)]:][:bg]
            it["i"] += 1
            nonlocal_state = tick.state
            st2, hit, *_ = sc.access(nonlocal_state, chunk,
                                     chunk.astype(np.int32))
            tick.state = st2
            return hit

        tick.state = st
        stats = time_chained_percentiles(tick)
        tick_p50[d] = bg / stats["p50"]
        records.append(_tp_record(
            f"sharded-jnp-shard{d}", bg, bg / stats["p50"] / 1e6,
            shards=d, per_shard_batch=tick_batch,
            p90_mops=round(bg / stats["p90"] / 1e6, 3),
            p50_req_s=round(bg / stats["p50"], 1),
            p90_req_s=round(bg / stats["p90"], 1)))

        # no-host-sync row: the whole trace in one scan, one sync at the end
        sc2 = ShardedCache(ShardedConfig(cache=kcfg, num_shards=d))
        rstats = time_replay_percentiles(
            lambda: sc2.replay(tr, bg), iters=3 if quick else 5)
        records.append(_tp_record(
            f"scan-shard{d}", bg, n_scan / rstats["p50"] / 1e6,
            shards=d, host_syncs_per_replay=1, n=n_scan,
            p50_req_s=round(n_scan / rstats["p50"], 1),
            p90_req_s=round(n_scan / rstats["p90"], 1)))

    for d in shards:
        records.append(_tp_record(
            f"scaling-shard{d}", d * tick_batch,
            tick_p50[d] / tick_p50[1], metric="speedup_x", shards=d))

    # comparable hit-ratio rows: the sharded batched replay vs the B=1 grid
    n_hr = QUICK_N if quick else FULL_N
    for d in (1, 4):
        for family in ("zipf", "scan_loop"):
            for pol in (Policy.LRU, Policy.LFU):
                if progress:
                    progress(f"hit-ratio {family}/{pol.name}/shard{d}")
                p = SweepPoint(family=family, policy=pol, assoc="k8",
                               capacity=1024, n=n_hr)
                hr = replay_sharded_point(p, shards=d, batch=256)
                records.append({
                    "id": f"{family}/{pol.name}/k8/jnp/shard{d}",
                    "family": family, "policy": pol.name, "assoc": "k8",
                    "shards": d, "batch": 256, "n": n_hr,
                    "capacity": p.capacity, "seed": p.seed,
                    "metric": "hit_ratio", "value": hr,
                    "comparable": True, "tol": 0.02,
                })

    spec = {"quick": quick, "shards": list(shards),
            "tick_batch": tick_batch, "n_scan": n_scan,
            "policy": policy.name, "capacity": THROUGHPUT_CAPACITY,
            "backend": "jnp"}
    return spec, records, []


def showdown(quick: bool = False, progress=None, threads=(1, 2, 4, 8),
             families=("zipf", "oltp_mix", "lirs_two_pools"),
             policies=("lru", "lfu")):
    """The paper's Fig. 1 analogue: req/s vs thread count, production caches
    next to our batched/resident paths (DESIGN.md §12).

    External rows (per family × policy), one per thread count in
    ``threads``:

      * ``cachetools-{policy}/threads{T}`` — ``cachetools.LRUCache``/
        ``LFUCache`` behind the documented global lock, T pool workers each
        replaying a contiguous trace slice against the shared cache;
      * ``striped-{policy}/threads{T}``   — the lock-striped pure-Python
        k-way baseline (one lock per set, same set hash as the device
        paths): limited associativity's structural benefit without SIMD.

    Our rows (same trace, same total capacity, k=8):

      * ``jnp-batched-{policy}/batch{B}``     — the chunked-scan batched
        replay (one jitted scan, one host sync);
      * ``pallas-resident-{policy}/batch{B}`` — the trace-resident replay
        megakernel (ONE launch, state pinned in VMEM).

    All throughput rows are wall-clock and ``comparable: false``.  The
    gateable output is the ``showdown-hr/...`` records: deterministic
    single-threaded hit ratios per library (cachetools is full-assoc
    LRU/LFU, striped and ours are k=8), ``comparable: true`` — CI diffs
    them against the committed baseline via the shared ``_baseline_gate``
    contract (exit 3 on breach).
    """
    from repro.core import trace_io, traces
    from repro.core.kway import KWayConfig
    from repro.core.simulate import SimConfig, replay_batched
    from repro.showdown import make_baseline, replay_threaded
    from repro.showdown import hit_ratio as baseline_hit_ratio

    capacity, ways, batch, seed = THROUGHPUT_CAPACITY, 8, 256, 7
    n = 8_192 if quick else 65_536
    iters = 2 if quick else 5
    trace_io.register_fixture_traces()   # lirs_two_pools rides as a family
    pol_enum = {"lru": Policy.LRU, "lfu": Policy.LFU}
    records = []
    trace_fp = {}

    def rec(rid, value, **extra):
        r = {"id": rid, "metric": "req_per_s", "value": round(value, 1),
             "capacity": capacity, "n": n, "comparable": False}
        r.update(extra)
        records.append(r)

    for family in families:
        tr = traces.generate(family, n, seed=seed)
        trace_fp[family] = trace_io.trace_fingerprint(tr)
        for policy in policies:
            # -- external libraries under threads -------------------------
            for lib in ("cachetools", "striped"):
                for t in threads:
                    if progress:
                        progress(f"{family}/{lib}-{policy} threads={t}")
                    cache = make_baseline(lib, capacity, policy, ways=ways)
                    st = replay_threaded(cache, tr, t, iters=iters)
                    rec(f"showdown/{family}/{lib}-{policy}/threads{t}",
                        st["req_s_p50"], family=family, lib=lib,
                        policy=policy, threads=t,
                        p90_req_s=round(st["req_s_p90"], 1),
                        reps_discarded=st["reps_discarded"])

            # -- our device paths (same trace, same capacity, k=8) --------
            kcfg = KWayConfig(num_sets=capacity // ways, ways=ways,
                              policy=pol_enum[policy])
            ours = (("jnp-batched", "jnp"), ("pallas-resident", "pallas"))
            hr_ours = {}
            for name, backend in ours:
                if progress:
                    progress(f"{family}/{name}-{policy}")
                sim = SimConfig(cache=kcfg, backend=backend)
                hr_ours[name] = replay_batched(sim, tr, batch=batch)  # + warm
                st = time_replay_percentiles(
                    lambda sim=sim: replay_batched(sim, tr, batch=batch),
                    iters=iters, warmup=1)
                rec(f"showdown/{family}/{name}-{policy}/batch{batch}",
                    n / st["p50"], family=family, lib=name, policy=policy,
                    batch=batch, p90_req_s=round(n / st["p90"], 1),
                    reps_discarded=st["reps_discarded"])

            # -- deterministic hit-ratio parity records (the gated rows) --
            hr = {
                "cachetools": baseline_hit_ratio(
                    make_baseline("cachetools", capacity, policy), tr),
                "striped": baseline_hit_ratio(
                    make_baseline("striped", capacity, policy, ways=ways),
                    tr),
                "jnp-batched": hr_ours["jnp-batched"],
                "pallas-resident": hr_ours["pallas-resident"],
            }
            for lib, value in hr.items():
                records.append({
                    "id": f"showdown-hr/{family}/{policy}/{lib}",
                    "family": family, "policy": policy, "lib": lib,
                    "capacity": capacity, "n": n, "seed": seed,
                    "batch": batch if lib.startswith(("jnp", "pallas"))
                    else None,
                    "metric": "hit_ratio", "value": round(float(value), 6),
                    "comparable": True, "tol": 1e-6,
                })

    spec = {"quick": quick, "families": list(families),
            "policies": list(policies), "threads": list(threads),
            "capacity": capacity, "ways": ways, "batch": batch,
            "n": n, "seed": seed, "trace_fingerprints": trace_fp}
    return spec, records, []


def synthetic_mix(quick: bool = False, progress=None, kinds=None):
    """Paper Figs. 27-30: fixed-hit-rate workloads per implementation."""
    if kinds is None:
        kinds = (("miss100", "hit95") if quick
                 else ("miss100", "hit100", "hit95", "hit90"))
    import jax
    import jax.numpy as jnp
    from repro.core import kway
    from repro.core.kway import KWayConfig, fully_associative

    capacity, batch = 4096, 512
    rng = np.random.default_rng(11)

    def mk_stream(kind, n):
        if kind == "miss100":   # every key unique
            return rng.permutation(np.arange(n, dtype=np.uint32) + (1 << 20))
        resident = rng.integers(0, capacity // 2, n).astype(np.uint32)
        if kind == "hit100":
            return resident
        p_miss = {"hit95": 0.05, "hit90": 0.10}[kind]
        miss = np.arange(n, dtype=np.uint32) + (1 << 20)
        take_miss = rng.random(n) < p_miss
        return np.where(take_miss, miss, resident).astype(np.uint32)

    impls = {
        "kway-soa": KWayConfig(num_sets=capacity // 8, ways=8,
                               policy=Policy.LRU),
        "sampled": KWayConfig(num_sets=capacity // 128, ways=128,
                              policy=Policy.LRU, sample=8),
        "full": fully_associative(capacity, Policy.LRU),
    }
    records = []
    for kind in kinds:
        if progress:
            progress(f"synthetic_mix {kind}")
        stream = mk_stream(kind, batch)
        for name, cfg in impls.items():
            state = kway.make_cache(cfg)
            resident = jnp.asarray(
                rng.integers(0, capacity // 2, capacity).astype(np.uint32))
            for chunk in resident.reshape(-1, 512):
                state, *_ = kway.access(cfg, state, chunk,
                                        chunk.astype(jnp.int32))
            keys = jnp.asarray(stream)
            fn = jax.jit(lambda s, k: kway.access(cfg, s, k,
                                                  k.astype(jnp.int32))[0])
            dt = time_jitted(fn, state, keys)
            records.append(_tp_record(f"{kind}/{name}", batch,
                                      batch / dt / 1e6))
    spec = {"quick": quick, "kinds": list(kinds), "capacity": capacity,
            "batch": batch}
    return spec, records, []


def serving(quick: bool = False, progress=None, requests=None, prefix_len=48):
    """End-to-end prefix-cache serving: tok/s, hit ratio, evictions."""
    import time as _time

    if requests is None:
        requests = 6 if quick else 12

    import jax
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import Engine, EngineConfig

    cfg = configs.get("deepseek-7b").smoke
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(1)
    shared = rng.integers(2, 400, prefix_len)
    prompts = [np.concatenate([shared, rng.integers(2, 400, 8)])
               for _ in range(requests)]
    records = []
    for policy in (Policy.LRU, Policy.LFU):
        if progress:
            progress(f"serving {policy.name}")
        eng = Engine(cfg, params, EngineConfig(
            page=8, num_sets=32, ways=8, policy=policy, max_batch=4,
            max_seq=256, private_pages=128))
        t0 = _time.time()
        for pr in prompts:
            eng.submit(pr, max_new=8)
        fin = eng.run()
        dt = _time.time() - t0
        toks = sum(len(r.generated) for r in fin.values())
        records.append({
            "id": f"{policy.name}/tok_per_s", "policy": policy.name,
            "metric": "tok_per_s", "value": round(toks / dt, 1),
            "comparable": False})
        records.append({
            "id": f"{policy.name}/prefix_hit_ratio", "policy": policy.name,
            "metric": "prefix_hit_ratio", "value": round(eng.hit_ratio(), 3),
            "comparable": True, "tol": 0.02})
        records.append({
            "id": f"{policy.name}/evictions", "policy": policy.name,
            "metric": "evictions", "value": int(eng.stats["evictions"]),
            "comparable": False})
    spec = {"quick": quick, "requests": requests, "prefix_len": prefix_len,
            "model": "deepseek-7b/smoke"}
    return spec, records, []


def serving_engine(quick: bool = False, progress=None, slots=None,
                   requests=None, max_new=4, decode_block=4):
    """Device-resident serving tick vs host-loop engine (DESIGN.md §11).

    Rows ``engine-{hostloop,jitted}-slots{S}``: p50/p90 requests/s and
    sustained tok/s over a shared-prefix continuous-batching workload, using
    the steady-state run-once protocol of ``time_replay_percentiles`` (each
    sample builds a FRESH engine and serves the whole request mix — the
    hostloop's per-request dispatches and the jitted engine's one-dispatch
    ticks are both inside the timed window; compiles are in the discarded
    warmup).  Short decodes (``max_new=4``) keep the workload
    admission-heavy — the regime where per-tick host round-trips dominate
    and the one-traced-program tick pays off.  Both engines run the same
    ``decode_block`` burst schedule (multi-step scheduling), so the speedup
    isolates dispatch/sync economics, not a schedule difference.

    Plus parity rows (comparable, tol 0): emitted tokens equal and identical
    prefix hit ratio between the two engines — the speedup headline is only
    meaningful if the jitted tick is indistinguishable semantically.
    """
    import jax

    from repro import configs
    from repro.eval.timing import time_replay_percentiles
    from repro.models import lm
    from repro.serve import Engine, EngineConfig

    if slots is None:
        slots = (32,) if quick else (8, 32)
    if requests is None:
        requests = 128 if quick else 192

    cfg = configs.get("deepseek-7b").smoke
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(1)
    shared = rng.integers(2, cfg.vocab_size - 1, 48)
    prompts = [np.concatenate([shared,
                               rng.integers(2, cfg.vocab_size - 1,
                                            int(rng.integers(4, 16)))])
               for _ in range(requests)]

    def engine(s, jitted):
        return Engine(cfg, params, EngineConfig(
            page=8, num_sets=64, ways=8, max_batch=s, max_seq=256,
            private_pages=512, max_prompt=128, decode_block=decode_block,
            jitted=jitted))

    def serve_all(s, jitted):
        eng = engine(s, jitted)
        for pr in prompts:
            eng.submit(pr, max_new=max_new)
        fin = eng.run()
        return eng, fin

    records = []
    for s in slots:
        stats = {}
        toks = {}
        gen = {}
        for jitted in (False, True):
            mode = "jitted" if jitted else "hostloop"
            if progress:
                progress(f"engine-{mode}-slots{s}")
            eng, fin = serve_all(s, jitted)      # parity + token count run
            gen[mode] = ({rid: list(r.generated) for rid, r in fin.items()},
                         eng.hit_ratio())
            toks[mode] = sum(len(r.generated) for r in fin.values())
            stats[mode] = time_replay_percentiles(
                lambda jitted=jitted: serve_all(s, jitted),
                iters=3 if quick else 5, warmup=1)
            records.append({
                "id": f"engine-{mode}-slots{s}/req_per_s",
                "impl": f"engine-{mode}", "slots": s,
                "requests": requests, "max_new": max_new,
                "metric": "req_per_s",
                "value": round(requests / stats[mode]["p50"], 1),
                "p90_req_s": round(requests / stats[mode]["p90"], 1),
                "tok_per_s": round(toks[mode] / stats[mode]["p50"], 1),
                "comparable": False})
        records.append({
            "id": f"engine-jitted-speedup-slots{s}",
            "slots": s, "metric": "speedup_x",
            "value": round(stats["hostloop"]["p50"] / stats["jitted"]["p50"],
                           2),
            "comparable": False})
        records.append({
            "id": f"engine-parity-slots{s}/tokens_equal",
            "slots": s, "metric": "tokens_equal",
            "value": float(gen["hostloop"][0] == gen["jitted"][0]),
            "comparable": True, "tol": 0.0})
        records.append({
            "id": f"engine-parity-slots{s}/hit_ratio",
            "slots": s, "metric": "prefix_hit_ratio",
            "value": round(gen["jitted"][1], 6),
            "scan_value": round(gen["hostloop"][1], 6),
            "comparable": True, "tol": 0.0})
    spec = {"quick": quick, "slots": list(slots), "requests": requests,
            "max_new": max_new, "decode_block": decode_block,
            "prefix_len": 48, "model": "deepseek-7b/smoke"}
    return spec, records, []


def robustness(quick: bool = False, progress=None, ttl: bool = False):
    """DESIGN.md §13: validator coverage, recovery cost, ladder
    observability, and validator overhead.

    Four record groups (``ttl=True`` adds a fifth, DESIGN.md §15):

      * ``robust-clean/{policy}/{backend}/violations`` — the invariant
        validator over the final state of the golden 512-request zipf
        trace, all 5 policies on the jnp and pallas backends (plus the
        sequential ref oracle on LRU; every policy in full mode).  Pinned
        at 0.0 with tol 0 — the zero-false-positive contract.
      * ``robust-scrub/{site}/...`` — inject one seeded bit-flip at the
        replay midpoint, scrub-and-invalidate, replay on: the recovered
        hit ratio and the forced-eviction tally, both deterministic from
        ``(seed, site, step)`` and pinned against the committed band.
      * ``robust-ladder/vmem-breach/...`` — replay under a forced
        zero-VMEM budget: the ladder must land on the chunked-scan rung,
        record observable degradation events, and still produce the clean
        run's exact hit count (rungs are pinned bit-identical).
      * ``robust-overhead/validated-replay/pct`` — wall-clock cost of
        fusing the validator into the replay scan at the quick cadence,
        vs the plain scan (``comparable: false``; the CLI gates the
        absolute <5% target).
      * ``robust-ttl/...`` (``ttl=True``) — the expiry lane: TTL replay
        of the golden trace with seeded per-request TTLs pinned clean
        under the STRICT expiry mode on jnp and pallas, backend hit
        parity pinned at zero diff, and the expiry-scrub chaos loop
        (``clock_skew``/``stale_entry`` injection -> strict scrub ->
        replay on) with its recovered hit ratio and forced-eviction
        tallies as the deterministic cost band.
    """
    from repro.core import backend as backend_mod
    from repro.core import trace_io, traces
    from repro.core.kway import KWayConfig
    from repro.core.router import pad_chunks
    from repro.robust import check_cache, events, faults, resilient_replay
    from repro.robust.ladder import RUNGS
    from repro.robust.recovery import scrub, validated_replay

    num_sets, ways, batch, seed = 16, 4, 8, 2026
    # the golden-trace recipe (tests/test_golden_trace.py)
    tr = traces.generate("zipf", 512, seed=seed, catalog=96)
    tr[::13] = 0
    chunks, enabled = pad_chunks(tr, batch)
    n = int(len(tr))
    records = []
    policies = {"lru": Policy.LRU, "lfu": Policy.LFU, "fifo": Policy.FIFO,
                "random": Policy.RANDOM, "hyperbolic": Policy.HYPERBOLIC}

    def cfg_for(pol):
        return KWayConfig(num_sets=num_sets, ways=ways, policy=pol)

    # ---- clean validator: zero false positives -------------------------
    for pname, pol in policies.items():
        cfg = cfg_for(pol)
        for backend in ("jnp", "pallas"):
            if progress:
                progress(f"clean {pname}/{backend}")
            be = backend_mod.make_backend(backend, cfg)
            _, _, st, _ = be.replay(be.init(), chunks, enabled)
            bad = int((np.asarray(check_cache(cfg, st, vals_mode="key")
                                  .lane_bits) != 0).sum())
            records.append({
                "id": f"robust-clean/{pname}/{backend}/violations",
                "policy": pname, "backend": backend, "n": n,
                "metric": "violating_lanes", "value": float(bad),
                "comparable": True, "tol": 0.0})
        ref_policies = ("lru",) if quick else tuple(policies)
        if pname in ref_policies:
            if progress:
                progress(f"clean {pname}/ref")
            be = backend_mod.make_backend("ref", cfg)
            st = be.init()
            for i in range(chunks.shape[0]):
                keys_i = np.asarray(chunks[i], np.uint32)
                st, _, _, _, _ = be.access(
                    st, keys_i, keys_i.astype(np.int32),
                    enabled=np.asarray(enabled[i]))
            bad = int((np.asarray(check_cache(cfg, st, vals_mode="key")
                                  .lane_bits) != 0).sum())
            records.append({
                "id": f"robust-clean/{pname}/ref/violations",
                "policy": pname, "backend": "ref", "n": n,
                "metric": "violating_lanes", "value": float(bad),
                "comparable": True, "tol": 0.0})

    # ---- scrub recovery: inject -> detect -> repair -> replay on -------
    cfg = cfg_for(Policy.LRU)
    be = backend_mod.make_backend("jnp", cfg)
    hits_clean, _, _, _ = be.replay(be.init(), chunks, enabled)
    hr_clean = float(np.asarray(hits_clean).sum()) / n
    records.append({
        "id": "robust-scrub/clean/hit_ratio", "site": None, "n": n,
        "metric": "hit_ratio", "value": round(hr_clean, 6),
        "comparable": True, "tol": 1e-6})
    half = chunks.shape[0] // 2
    for site in ("keys", "fprint", "meta_a"):
        if progress:
            progress(f"scrub {site}")
        h1, _, st, _ = be.replay(be.init(), chunks[:half], enabled[:half])
        st, _ = faults.flip_bit(st, site, seed=seed, step=half)
        st, forced, _ = scrub(cfg, st, vals_mode="key")
        h2, _, st, _ = be.replay(st, chunks[half:], enabled[half:])
        hr = (float(np.asarray(h1).sum()) + float(np.asarray(h2).sum())) / n
        records.append({
            "id": f"robust-scrub/{site}/hit_ratio", "site": site, "n": n,
            "seed": seed, "step": half, "metric": "hit_ratio",
            "value": round(hr, 6), "clean_value": round(hr_clean, 6),
            "comparable": True, "tol": 1e-6})
        records.append({
            "id": f"robust-scrub/{site}/forced_evictions", "site": site,
            "seed": seed, "step": half, "metric": "forced_evictions",
            "value": float(int(forced)), "comparable": True, "tol": 0.0})

    # ---- degradation ladder under a forced VMEM breach -----------------
    if progress:
        progress("ladder vmem-breach")
    c0 = events.cursor()
    with backend_mod.vmem_budget(0):
        out = resilient_replay(cfg, chunks, enabled)
    n_events = len(events.since(c0))
    records.append({
        "id": "robust-ladder/vmem-breach/rung", "metric": "ladder_rung",
        "rung": out.rung, "value": float(RUNGS.index(out.rung)),
        "comparable": True, "tol": 0.0})
    records.append({
        "id": "robust-ladder/vmem-breach/hit_ratio", "metric": "hit_ratio",
        "value": round(float(np.asarray(out.hits).sum()) / n, 6),
        "clean_value": round(hr_clean, 6),
        "comparable": True, "tol": 1e-6})
    records.append({
        "id": "robust-ladder/vmem-breach/events", "metric": "event_count",
        "value": float(n_events), "comparable": False})

    # ---- expiry lane: TTL parity + expiry-scrub cost band (§15) --------
    if ttl:
        from repro.core.simulate import _pad_ttl_chunks

        ttl_rng = np.random.default_rng(seed + 1)
        tt = _pad_ttl_chunks(ttl_rng.integers(0, 200, n).astype(np.int32),
                             batch)
        ttl_hits = {}
        for backend in ("jnp", "pallas"):
            if progress:
                progress(f"ttl clean {backend}")
            be_t = backend_mod.make_backend(backend, cfg)
            h, _, st, _ = be_t.replay(be_t.init(ttl=True), chunks, enabled,
                                      ttls=tt)
            ttl_hits[backend] = float(np.asarray(h).sum())
            bad = int((np.asarray(check_cache(cfg, st, vals_mode="key")
                                  .lane_bits) != 0).sum())
            records.append({
                "id": f"robust-ttl/clean/{backend}/violations",
                "backend": backend, "n": n, "metric": "violating_lanes",
                "value": float(bad), "comparable": True, "tol": 0.0})
        hr_ttl = ttl_hits["jnp"] / n
        records.append({
            "id": "robust-ttl/parity/hit_ratio", "n": n,
            "metric": "hit_ratio", "value": round(hr_ttl, 6),
            "comparable": True, "tol": 1e-6})
        records.append({
            "id": "robust-ttl/parity/backend_max_diff", "n": n,
            "metric": "hit_diff",
            "value": abs(ttl_hits["jnp"] - ttl_hits["pallas"]),
            "comparable": True, "tol": 0.0})
        for site_name, inject in (("clock_skew", faults.clock_skew),
                                  ("stale_entry", faults.stale_entry)):
            if progress:
                progress(f"ttl scrub {site_name}")
            h1, _, st, _ = be.replay(be.init(ttl=True), chunks[:half],
                                     enabled[:half], ttls=tt[:half])
            st, _ = inject(st, seed=seed, step=half)
            st, forced, _ = scrub(cfg, st, vals_mode="key")
            h2, _, st, _ = be.replay(st, chunks[half:], enabled[half:],
                                     ttls=tt[half:])
            hr = (float(np.asarray(h1).sum())
                  + float(np.asarray(h2).sum())) / n
            records.append({
                "id": f"robust-ttl/scrub/{site_name}/hit_ratio",
                "site": site_name, "n": n, "seed": seed, "step": half,
                "metric": "hit_ratio", "value": round(hr, 6),
                "clean_value": round(hr_ttl, 6),
                "comparable": True, "tol": 1e-6})
            records.append({
                "id": f"robust-ttl/scrub/{site_name}/forced_evictions",
                "site": site_name, "seed": seed, "step": half,
                "metric": "forced_evictions", "value": float(int(forced)),
                "comparable": True, "tol": 0.0})

    # ---- validator overhead on the quick replay ------------------------
    interval = 1
    ov_sets, ov_ways, ov_batch = 512, 8, 256
    ov_n = 8_192 if quick else 65_536
    iters = 3 if quick else 5
    if progress:
        progress(f"overhead n={ov_n} interval={interval}")
    ov_cfg = KWayConfig(num_sets=ov_sets, ways=ov_ways, policy=Policy.LRU)
    ov_tr = traces.generate("zipf", ov_n, seed=7)
    ov_chunks, ov_enabled = pad_chunks(ov_tr, ov_batch)
    ov_be = backend_mod.make_backend("jnp", ov_cfg)

    def plain():
        h, _, _, _ = ov_be.replay(ov_be.init(), ov_chunks, ov_enabled)
        return int(np.asarray(h).sum())

    def validated():
        h, _, _, _, alarm = validated_replay(
            ov_cfg, ov_chunks, ov_enabled, interval=interval,
            vals_mode="key")
        return int(np.asarray(h).sum()) + int(alarm) * 0

    t_plain = time_replay_percentiles(plain, iters=iters, warmup=1)
    t_val = time_replay_percentiles(validated, iters=iters, warmup=1)
    pct = (t_val["p50"] - t_plain["p50"]) / t_plain["p50"] * 100.0
    records.append({
        "id": "robust-overhead/validated-replay/pct",
        "metric": "overhead_pct", "value": round(pct, 2),
        "interval": interval, "n": ov_n, "batch": ov_batch,
        "capacity": ov_sets * ov_ways,
        "plain_p50_s": round(t_plain["p50"], 6),
        "validated_p50_s": round(t_val["p50"], 6),
        "comparable": False})

    spec = {"quick": quick, "ttl": ttl, "num_sets": num_sets, "ways": ways,
            "batch": batch, "n": n, "seed": seed,
            "trace_fingerprint": trace_io.trace_fingerprint(tr),
            "scrub_sites": ["keys", "fprint", "meta_a"],
            "overhead": {"num_sets": ov_sets, "ways": ov_ways,
                         "batch": ov_batch, "n": ov_n,
                         "interval": interval}}
    return spec, records, []


def hierarchy(quick: bool = False, progress=None):
    """Two-level replay hierarchy (DESIGN.md §14): throughput and hit ratio
    vs total capacity across the L1-size knob.

    Timing rows (``hier-tp/...``, not comparable): whole-trace replay req/s
    at two L2 capacities — one where the flat megakernel still fits its
    VMEM budget (the hierarchy must not cost much) and one past the
    capacity cliff where the flat path has demoted to the chunked scan
    (the hierarchy must win big, because its VMEM footprint is set by
    ``l1_sets`` alone).  ``hier-tp/speedup/s{S}`` is flat-p50 over
    l1l2-p50; past the cliff the CI gate pins it >= 2x.

    Hit-ratio rows (``hier-hr/{family}/l1-{K}``, comparable): a fixed
    64x8 L2 with the L1-size knob swept over {0, 16, 64} sets x 16 ways.
    ``l1-0`` records carry ``scan_value`` (the flat replay on the same
    config) and tol 0.0 — the disabled hierarchy IS the flat path,
    bit-exact.  Enabled records carry ``flat_value`` — a flat cache of
    the same TOTAL capacity (64x12 / 64x24) — as the oracle reference,
    and gate against the checked-in baseline with tol 0.02.
    """
    from repro.core import backend as backend_mod
    from repro.core import trace_io, traces
    from repro.core.hierarchy import HierarchyConfig, hier_footprint_bytes
    from repro.core.kway import KWayConfig
    from repro.core.simulate import SimConfig, replay_batched

    policy = Policy.LRU
    batch = 256
    n = 16_384 if quick else 65_536
    hier = HierarchyConfig(l1_sets=64, l1_ways=16)
    l2_sets_sweep = (512, 4096)
    tr = traces.generate("zipf", n, seed=7, catalog=1 << 17)
    records = []

    for l2_sets in l2_sets_sweep:
        cfg = KWayConfig(num_sets=l2_sets, ways=8, policy=policy)
        pb = backend_mod.make_backend("pallas", cfg)
        flat_fits = pb.resident_fits()
        sim = SimConfig(cache=cfg, backend="pallas")
        p50 = {}
        for mode, hcfg, path in (
                ("flat", None,
                 "pallas-resident" if flat_fits else "pallas-scan"),
                ("l1l2", hier, "pallas-resident-l1l2")):
            if progress:
                progress(f"hier timing {mode} s{l2_sets}")
            st = time_replay_percentiles(
                lambda _h=hcfg: replay_batched(sim, tr, batch=batch,
                                               hierarchy=_h),
                iters=3 if quick else 5)
            p50[mode] = st["p50"]
            records.append(_tp_record(
                f"hier-tp/{mode}/s{l2_sets}", batch, n / st["p50"] / 1e6,
                n=n, mode=mode, path=path, l2_sets=l2_sets,
                l2_capacity=cfg.capacity, over_budget=not flat_fits,
                p50_req_s=round(n / st["p50"], 1),
                p90_req_s=round(n / st["p90"], 1),
                reps_discarded=st["reps_discarded"]))
        records.append(_tp_record(
            f"hier-tp/speedup/s{l2_sets}", batch,
            p50["flat"] / p50["l1l2"],
            metric="speedup_x", l2_sets=l2_sets,
            over_budget=not flat_fits))

    # hit ratio vs total capacity across the L1-size knob
    trace_io.register_fixture_traces()
    n_hr = QUICK_N if quick else 16_384
    hr_batch = 64
    l2_hr = KWayConfig(num_sets=64, ways=8, policy=policy)
    for family in ("zipf", "lirs_two_pools"):
        kwargs = {"catalog": 4096} if family == "zipf" else {}
        trh = traces.generate(family, n_hr, seed=7, **kwargs)
        sim = SimConfig(cache=l2_hr, backend="pallas")
        for l1_sets in (0, 16, 64):
            if progress:
                progress(f"hier-hr {family} l1-{l1_sets}")
            hcfg = HierarchyConfig(l1_sets=l1_sets, l1_ways=16)
            hr = replay_batched(sim, trh, batch=hr_batch, hierarchy=hcfg)
            total = l2_hr.capacity + hcfg.l1_capacity
            rec = {
                "id": f"hier-hr/{family}/l1-{l1_sets}",
                "family": family, "policy": policy.name,
                "l1_sets": l1_sets, "l1_ways": hcfg.l1_ways,
                "l2_capacity": l2_hr.capacity, "total_capacity": total,
                "batch": hr_batch, "n": n_hr,
                "metric": "hit_ratio", "value": hr, "comparable": True,
            }
            if l1_sets == 0:
                rec["scan_value"] = replay_batched(sim, trh, batch=hr_batch)
                rec["tol"] = 0.0
            else:
                flat = KWayConfig(num_sets=64, ways=total // 64,
                                  policy=policy)
                rec["flat_value"] = replay_batched(
                    SimConfig(cache=flat, backend="pallas"), trh,
                    batch=hr_batch)
                rec["tol"] = 0.02
            records.append(rec)

    spec = {"quick": quick, "batch": batch, "n": n, "n_hr": n_hr,
            "hr_batch": hr_batch, "policy": policy.name,
            "l2_sets": list(l2_sets_sweep), "l2_ways": 8,
            "l1_sets": hier.l1_sets, "l1_ways": hier.l1_ways,
            "l1_footprint_bytes": hier_footprint_bytes(hier),
            "vmem_budget": backend_mod.RESIDENT_VMEM_BUDGET}
    return spec, records, []


#: CLI name -> (function, canonical figure name)
FIGURES = {
    "hit_ratio": (hit_ratio_vs_associativity, "hit_ratio_vs_associativity"),
    "sampled_vs_limited": (sampled_vs_limited, "sampled_vs_limited"),
    "admission": (admission_ablation, "admission_ablation"),
    "throughput": (throughput_vs_batch, "throughput_vs_batch"),
    "throughput_resident": (throughput_resident, "throughput_resident"),
    "throughput_shards": (throughput_vs_shards, "throughput_vs_shards"),
    "showdown": (showdown, "showdown"),
    "synthetic_mix": (synthetic_mix, "synthetic_mix"),
    "serving": (serving, "serving"),
    "serving_engine": (serving_engine, "serving_engine"),
    "robustness": (robustness, "robustness"),
    "hierarchy": (hierarchy, "hierarchy"),
}
