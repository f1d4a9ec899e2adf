"""Declarative sweep runner — the measurement engine behind every figure.

A sweep is a grid of (trace family × policy × associativity × backend ×
admission × seed) points, all replayed with the exact sequential semantics of
``core/simulate.replay`` (B=1: get at logical time t, put-on-miss at t+1).

The speed trick (DESIGN.md §7): points whose cache *shape* matches are
stacked along a leading config axis and replayed by ONE compiled
``lax.scan`` whose step is ``vmap``-ed over the stack.  Two things make the
stack wide:

  * traces are data — every (family, seed) pair rides the same compilation;
  * the eviction policy is data too — ``policies.victim_scores_dyn`` and
    friends dispatch on a *traced* policy index, so LRU/LFU/FIFO/RANDOM/
    HYPERBOLIC all share one program (jnp path).

The pallas path keeps the policy static (the kernel specializes victim
scoring at trace time), so its groups are per (shape × policy) — still
independent of families and seeds.  Net effect: a quick grid of
``4 families × 3 policies × 5 associativities × 2 backends`` compiles
O(shapes) programs, not O(configs); ``trace_counts()`` exposes the actual
compile tally and tests assert on it.

Replay here *is* the jnp/pallas backend semantics at batch size 1 — the
equivalence test (tests/test_eval_runner.py) pins runner hit counts to
``simulate.replay`` bit-for-bit, per policy, including sampled and
fully-associative shapes.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import admission, hashing, kway, traces
from repro.core.hashing import EMPTY_KEY
from repro.core.kway import NEG_INF, KWayConfig
from repro.core.policies import (Policy, on_hit, on_hit_dyn, on_insert,
                                 on_insert_dyn, victim_scores_dyn)

HASH_SEED = KWayConfig.__dataclass_fields__["seed"].default

# Trace-time side effect: each body below bumps its group key once per XLA
# compilation, so tests can assert "O(shapes), not O(configs)" directly.
_TRACE_COUNTS: collections.Counter = collections.Counter()


def trace_counts() -> dict:
    """Compilation tally of the stacked replay kernels, keyed by group."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


# ---------------------------------------------------------------------------
# sweep grid
# ---------------------------------------------------------------------------

#: associativity descriptors: name -> (num_sets, ways, sample) for a capacity
def assoc_shape(assoc: str, capacity: int) -> tuple[int, int, int]:
    """Resolve an associativity descriptor ("k8", "sampled8", "full")."""
    if assoc == "full":
        return 1, capacity, 0
    if assoc.startswith("sampled"):
        return 1, capacity, int(assoc[len("sampled"):])
    if assoc.startswith("k"):
        k = int(assoc[1:])
        if capacity % k:
            raise ValueError(f"capacity {capacity} not divisible by k={k}")
        return capacity // k, k, 0
    raise ValueError(f"unknown associativity descriptor {assoc!r}")


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One cell of a hit-ratio grid (a single replay)."""

    family: str
    policy: Policy
    assoc: str                 # "k4" | "sampled8" | "full" | ...
    capacity: int
    backend: str = "jnp"
    admission: str = "none"    # "none" | "tinylfu"
    seed: int = 42
    n: int = 60_000

    @property
    def shape(self) -> tuple[int, int, int]:
        return assoc_shape(self.assoc, self.capacity)

    @property
    def record_id(self) -> str:
        """Stable identity for baseline joins (seed-independent)."""
        return (f"{self.family}/{self.policy.name}/{self.assoc}"
                f"/{self.backend}/{self.admission}")


@dataclasses.dataclass(frozen=True)
class HitRatioSpec:
    """A declarative grid; ``expand()`` yields the supported points."""

    families: tuple = ("zipf", "zipf_shift", "scan_loop", "oltp_mix")
    policies: tuple = (Policy.LRU, Policy.LFU, Policy.HYPERBOLIC)
    assoc: tuple = ("k4", "k8", "k32", "sampled8", "full")
    backends: tuple = ("jnp",)
    admissions: tuple = ("none",)
    capacity: int = 1024
    n: int = 60_000
    seeds: tuple = (42,)
    # family -> extra kwargs for traces.generate, e.g.
    # {"scan_loop": {"working": 1536, "noise": 0.1}}
    trace_kwargs: dict = dataclasses.field(default_factory=dict)

    def expand(self) -> tuple[list[SweepPoint], list[str]]:
        """-> (points, skipped) — skipped lists unsupported combos loudly."""
        points, skipped = [], []
        for fam in self.families:
            for pol in self.policies:
                for assoc in self.assoc:
                    s, k, sample = assoc_shape(assoc, self.capacity)
                    for be in self.backends:
                        reason = _backend_unsupported(be, k, sample)
                        if reason:
                            skipped.append(
                                f"{fam}/{pol.name}/{assoc}/{be}: {reason}")
                            continue
                        for adm in self.admissions:
                            for seed in self.seeds:
                                points.append(SweepPoint(
                                    family=fam, policy=pol, assoc=assoc,
                                    capacity=self.capacity, backend=be,
                                    admission=adm, seed=seed, n=self.n))
        return points, sorted(set(skipped))

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["policies"] = [p.name for p in self.policies]
        return d


def _backend_unsupported(backend: str, ways: int, sample: int) -> Optional[str]:
    if backend == "pallas":
        from repro.kernels import kway_probe as _kp
        if sample:
            return "pallas backend does not support sampled policies"
        if ways > _kp.LANES:
            return f"pallas backend requires ways <= {_kp.LANES}"
    elif backend == "ref":
        return ("ref backend is the sequential Python oracle, not a sweep "
                "substrate (use the golden differential tests)")
    elif backend != "jnp":
        return f"unknown backend {backend!r}"
    return None


# ---------------------------------------------------------------------------
# stacked replay kernels
#
# State is a stack of per-config caches: keys/meta [C, S, K], clock [C].
# One scan step replays one request per config lane, reproducing the
# sequential backend semantics exactly: get at time `clock` (hit -> on_hit
# metadata), put-on-miss at time `clock + 1` (victim scored then), clock += 2.
# ---------------------------------------------------------------------------

def _victim_way(num_sets, ways, sample, pidx, keys_row, ma_row, mb_row, now):
    """Victim way of one set row at logical time `now` (B=1 semantics of
    core/kway._victim_order: empty ways first, sampled draw when sample>0)."""
    if 0 < sample < ways:
        way_ids = kway.sampled_way_ids(sample, ways, now)
        ks = keys_row[way_ids]
        scores = victim_scores_dyn(
            pidx, ma_row[way_ids], mb_row[way_ids], now, ks)
        scores = jnp.where(ks == EMPTY_KEY, NEG_INF, scores)
        return way_ids[jnp.argmin(scores)]
    scores = victim_scores_dyn(pidx, ma_row, mb_row, now, keys_row)
    scores = jnp.where(keys_row == EMPTY_KEY, NEG_INF, scores)
    return jnp.argmin(scores).astype(jnp.int32)


def _scan_replay(init_lane, step_lane, trace_cn, tinylfu):
    """Shared scan harness: vmap `step_lane` over the config stack."""
    C, _ = trace_cn.shape
    lanes = jax.vmap(init_lane)(jnp.arange(C))
    sketch = (jax.vmap(lambda _: admission.make_sketch(tinylfu))(jnp.arange(C))
              if tinylfu else jnp.zeros((C,), jnp.int32))
    vstep = jax.vmap(step_lane)

    def step(carry, keys_c):
        lanes, sketch, hits = carry
        lanes, sketch, hit = vstep(lanes, sketch, keys_c)
        return (lanes, sketch, hits + hit.astype(jnp.int32)), ()

    (_, _, hits), _ = jax.lax.scan(
        step, (lanes, sketch, jnp.zeros((C,), jnp.int32)), trace_cn.T)
    return hits


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _replay_group_jnp(num_sets, ways, sample, hash_seed, tinylfu,
                      pidx, trace_cn):
    """One compiled replay for a stack of same-shape jnp configs.

    pidx int32 [C] (traced policy index), trace_cn uint32 [C, N] -> hits [C].
    """
    _TRACE_COUNTS[("jnp", num_sets, ways, sample, trace_cn.shape[1],
                   tinylfu is not None)] += 1

    # The per-lane policy index rides inside the lane tuple so one vmap maps
    # state, sketch, keys and policy together.
    def init_lane(i):
        return (jnp.full((num_sets, ways), EMPTY_KEY, jnp.uint32),
                jnp.zeros((num_sets, ways), jnp.int32),
                jnp.zeros((num_sets, ways), jnp.int32),
                jnp.zeros((), jnp.int32),
                pidx[i])

    def step_lane(lane, sketch, raw):
        keys, ma, mb, clock, p = lane
        (keys, ma, mb, clock), sketch, hit = _step_jnp(
            num_sets, ways, sample, hash_seed, tinylfu,
            p, keys, ma, mb, clock, sketch, raw)
        return (keys, ma, mb, clock, p), sketch, hit

    return _scan_replay(init_lane, step_lane, trace_cn, tinylfu)


def _step_jnp(num_sets, ways, sample, hash_seed, tinylfu,
              pidx1, keys, ma, mb, clock, sketch, raw):
    """One request through one config lane (jnp probe, dynamic policy)."""
    qkey = hashing.sanitize_keys(raw[None])[0]
    s = hashing.set_index(qkey[None], num_sets, hash_seed)[0]
    row = keys[s]
    eq = (row == qkey) & (row != EMPTY_KEY)
    hit = jnp.any(eq)
    way = jnp.argmax(eq).astype(jnp.int32)

    ok = jnp.bool_(True)
    if tinylfu is not None:
        # Phase order of simulate._replay_scan: record, peek victim at time
        # `clock` (pre-get), admission-gate the miss insert.
        sketch = admission.record(tinylfu, sketch, qkey[None])
        vway0 = _victim_way(num_sets, ways, sample, pidx1, row, ma[s], mb[s],
                            clock)
        vkey0 = row[vway0]
        vvalid = (vkey0 != EMPTY_KEY) & ~hit
        ok = admission.admit(tinylfu, sketch, qkey[None], vkey0[None],
                             vvalid[None])[0]

    # get phase at time `clock`
    ha, hb = on_hit_dyn(pidx1, ma[s, way], mb[s, way], clock)
    ma = ma.at[s, way].set(jnp.where(hit, ha, ma[s, way]))
    mb = mb.at[s, way].set(jnp.where(hit, hb, mb[s, way]))

    # put phase at time `clock + 1`, miss lanes only (hit lanes are disabled
    # in access(); a miss leaves the metadata untouched, so scoring the
    # post-get state equals scoring the pre-get state here)
    t_put = clock + 1
    vway = _victim_way(num_sets, ways, sample, pidx1, row, ma[s], mb[s], t_put)
    ia, ib = on_insert_dyn(pidx1, t_put)
    do = ~hit & ok
    keys = keys.at[s, vway].set(jnp.where(do, qkey, keys[s, vway]))
    ma = ma.at[s, vway].set(jnp.where(do, ia, ma[s, vway]))
    mb = mb.at[s, vway].set(jnp.where(do, ib, mb[s, vway]))
    return (keys, ma, mb, clock + 2), sketch, hit


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _replay_group_pallas(num_sets, ways, hash_seed, policy, tinylfu, trace_cn):
    """One compiled replay for a stack of same-shape pallas configs.

    The kernel specializes the policy at trace time, so the stack spans
    (family × seed) only; trace_cn uint32 [C, N] -> hits [C].
    """
    from repro.kernels import kway_probe as _kp
    _TRACE_COUNTS[("pallas", num_sets, ways, 0, trace_cn.shape[1],
                   tinylfu is not None, int(policy))] += 1
    qt = 8

    def pad_ways(arr, fill=-1):
        s, k = arr.shape
        if k == _kp.LANES:
            return arr
        return jnp.concatenate(
            [arr, jnp.full((s, _kp.LANES - k), fill, arr.dtype)], axis=1)

    def probe1(keys, fpr, ma, mb, qkey, t):
        """Kernel probe of one query; scalar outputs (s, hit, way, vway)."""
        sets = hashing.set_index(qkey[None], num_sets, hash_seed)
        zpad = jnp.zeros((qt - 1,), jnp.int32)
        hit, way, vway, _ = _kp.kway_probe(
            pad_ways(keys.astype(jnp.int32)),
            pad_ways(fpr.astype(jnp.int32), fill=0),
            pad_ways(ma), pad_ways(mb),
            jnp.concatenate([sets, zpad]),
            jnp.concatenate([qkey[None].astype(jnp.int32), zpad]),
            jnp.concatenate([t[None], zpad]),
            policy=int(policy), ways=ways, qt=qt, full_order=False)
        return sets[0], hit[0].astype(jnp.bool_), way[0], vway[0]

    def init_lane(_):
        return (jnp.full((num_sets, ways), EMPTY_KEY, jnp.uint32),
                jnp.zeros((num_sets, ways), jnp.uint32),   # fingerprints
                jnp.zeros((num_sets, ways), jnp.int32),
                jnp.zeros((num_sets, ways), jnp.int32),
                jnp.zeros((), jnp.int32))

    def step_lane(lane, sketch, raw):
        keys, fpr, ma, mb, clock = lane
        qkey = hashing.sanitize_keys(raw[None])[0]
        t_put = clock + 1
        # One probe at t_put serves both phases: hit/way are time-independent
        # and a miss leaves the get-phase metadata untouched, so the victim
        # scored on the pre-get state at t_put matches PallasBackend.put.
        s, hit, way, vway = probe1(keys, fpr, ma, mb, qkey, t_put)

        ok = jnp.bool_(True)
        if tinylfu is not None:
            # peek_victims probes at time `clock` (pre-get) — a separate
            # kernel probe because RANDOM victim scores depend on the time.
            sketch = admission.record(tinylfu, sketch, qkey[None])
            _, _, _, vway0 = probe1(keys, fpr, ma, mb, qkey, clock)
            vkey0 = keys[s, vway0]
            vvalid = (vkey0 != EMPTY_KEY) & ~hit
            ok = admission.admit(tinylfu, sketch, qkey[None], vkey0[None],
                                 vvalid[None])[0]

        ha, hb = on_hit(policy, ma[s, way], mb[s, way], clock)
        ma = ma.at[s, way].set(jnp.where(hit, ha, ma[s, way]))
        mb = mb.at[s, way].set(jnp.where(hit, hb, mb[s, way]))
        ia, ib = on_insert(policy, t_put)
        do = ~hit & ok
        keys = keys.at[s, vway].set(jnp.where(do, qkey, keys[s, vway]))
        fpr = fpr.at[s, vway].set(jnp.where(
            do, hashing.fingerprint(qkey[None])[0], fpr[s, vway]))
        ma = ma.at[s, vway].set(jnp.where(do, ia, ma[s, vway]))
        mb = mb.at[s, vway].set(jnp.where(do, ib, mb[s, vway]))
        return (keys, fpr, ma, mb, clock + 2), sketch, hit

    return _scan_replay(init_lane, step_lane, trace_cn, tinylfu)


# ---------------------------------------------------------------------------
# sharded replay of grid points
# ---------------------------------------------------------------------------

def replay_sharded_point(point: SweepPoint, shards: int, batch: int = 256,
                         trace: Optional[np.ndarray] = None) -> float:
    """Hit ratio of one sweep-grid point replayed through the set-sharded
    batched path (``simulate.replay_batched`` with ``shards=D`` — a single
    jitted ``lax.scan`` with device-resident routing since PR 4).

    Batched conflict resolution perturbs hit ratios slightly relative to the
    grid's exact B=1 replay, so callers gate these values against the B=1
    baselines with a small band (DESIGN.md §9), not bit-exactly.
    """
    from repro.core import simulate, traces as _traces

    s, k, sample = point.shape
    cfg = KWayConfig(num_sets=s, ways=k, policy=point.policy, sample=sample)
    tlfu = (admission.for_capacity(point.capacity)
            if point.admission == "tinylfu" else None)
    if trace is None:
        trace = _traces.generate(point.family, point.n, seed=point.seed)
    sim = simulate.SimConfig(cache=cfg, tinylfu=tlfu, backend=point.backend)
    return simulate.replay_batched(sim, trace, batch=batch, shards=shards)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _trace_cache(points: list[SweepPoint], trace_kwargs: dict) -> dict:
    cache = {}
    for p in points:
        key = (p.family, p.seed, p.n)
        if key not in cache:
            cache[key] = traces.generate(
                p.family, p.n, seed=p.seed, **trace_kwargs.get(p.family, {}))
    return cache


def run_hit_ratio_sweep(spec: HitRatioSpec, progress=None):
    """Execute the grid.  Returns (records, skipped).

    Each record aggregates one grid cell over ``spec.seeds``:
    ``{"id", "figure"-free config fields, "metric": "hit_ratio",
    "value": mean, "per_seed": [...], "comparable": True}``.
    """
    points, skipped = spec.expand()
    tr = _trace_cache(points, spec.trace_kwargs)
    tlfu = admission.for_capacity(spec.capacity)

    groups: dict = collections.defaultdict(list)
    for p in points:
        s, k, sample = p.shape
        adm = tlfu if p.admission == "tinylfu" else None
        if p.backend == "pallas":
            gkey = ("pallas", s, k, sample, p.n, adm, p.policy)
        else:
            gkey = ("jnp", s, k, sample, p.n, adm)
        groups[gkey].append(p)

    counts_before = collections.Counter(_TRACE_COUNTS)
    hit_ratio: dict[SweepPoint, float] = {}
    for gkey, pts in groups.items():
        backend, s, k, sample, n, adm = gkey[:6]
        if progress:
            progress(f"group {backend}/S{s}xK{k}"
                     f"{f'/sample{sample}' if sample else ''} "
                     f"({len(pts)} configs stacked)")
        trace_cn = jnp.asarray(
            np.stack([tr[(p.family, p.seed, p.n)] for p in pts]))
        if backend == "pallas":
            hits = _replay_group_pallas(s, k, HASH_SEED, gkey[6], adm,
                                        trace_cn)
        else:
            pidx = jnp.asarray([int(p.policy) for p in pts], jnp.int32)
            hits = _replay_group_jnp(s, k, sample, HASH_SEED, adm,
                                     pidx, trace_cn)
        for p, h in zip(pts, np.asarray(hits)):
            hit_ratio[p] = float(h) / p.n

    # Compile economy invariant: the (now fused single-probe) stacked replay
    # must still compile once per cache *shape* group, never once per config.
    # Each group triggers at most one fresh trace (jit may also reuse an
    # earlier sweep's program, hence <=, not ==); a regression that makes the
    # step retrace per stacked lane would blow past len(groups) immediately.
    new_compiles = sum((collections.Counter(_TRACE_COUNTS)
                        - counts_before).values())
    assert new_compiles <= len(groups), (
        f"stacked sweep compiled {new_compiles} replay programs for "
        f"{len(groups)} shape groups — the fused replay step is being "
        "retraced per config instead of once per cache shape")

    records = []
    seen = set()
    for p in points:
        if p.record_id in seen:
            continue
        seen.add(p.record_id)
        per_seed = [hit_ratio[dataclasses.replace(p, seed=sd)]
                    for sd in spec.seeds]
        s, k, sample = p.shape
        records.append({
            "id": p.record_id,
            "family": p.family, "policy": p.policy.name, "assoc": p.assoc,
            "num_sets": s, "ways": k, "sample": sample,
            "capacity": p.capacity, "backend": p.backend,
            "admission": p.admission, "n": p.n, "seeds": list(spec.seeds),
            "metric": "hit_ratio",
            "value": float(np.mean(per_seed)),
            "per_seed": per_seed,
            "comparable": True,
        })
    return records, skipped
