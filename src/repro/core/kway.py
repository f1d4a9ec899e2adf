"""K-way set-associative cache — the paper's core, as a functional JAX module.

The cache is a pytree of dense, fixed-shape arrays (the paper's "static
memory, no pointers" claim maps one-to-one onto jit/pjit requirements):

    keys    uint32[S, k]   stored keys (EMPTY_KEY sentinel = empty way)
    fprint  uint32[S, k]   16-bit fingerprints (SoA / KW-WFSC layout only)
    vals    int32 [S, k]   payload (e.g. KV-page index, object handle)
    meta_a  int32 [S, k]   policy lane A (LRU ts / LFU count / hyperbolic n)
    meta_b  int32 [S, k]   policy lane B (hyperbolic t0)
    clock   int32 []       global logical clock (paper: per-set AtomicLong)

Concurrency adaptation (see DESIGN.md §2): the paper's T threads become a
batch of B requests per step.  Requests to different sets are data-independent
(the paper's embarrassing parallelism) and are processed by pure vector ops.
Requests that collide on one set are resolved deterministically:

  * duplicate keys within a batch: the first occurrence performs the insert,
    later ones are dropped (the CAS-race outcome in KW-WFA);
  * distinct missing keys in one set: the i-th such request takes the i-th
    worst victim of that set (rank-ordered victim selection — the retry loop
    of KW-WFA collapsed into one vectorized pass).  At most k admissions per
    set per batch; overflow requests are not admitted (bounded, deterministic).

Layouts: ``soa`` (KW-WFSC — separate key/fingerprint/counter arrays, scans
touch contiguous memory, the TPU-friendly default) and ``aos`` (KW-WFA — one
interleaved record array [S, k, 4], gathered as records; kept as the layout
baseline the paper also measures).

The fully-associative oracle is *this same cache* with ``num_sets=1,
ways=capacity`` — the paper's observation that full associativity is the
degenerate corner of the design space.

Each phase of an operation runs under a ``jax.named_scope``: ``kway.scrub``
(expiry scrub), ``kway.probe`` (set index, gather, key compare), ``kway.hit``
(hit-side metadata update and value gather), ``kway.victims`` (victim
order), ``kway.resolve`` (intra-batch dedupe and rank) and ``kway.insert``
(evicted-key gather, lane scatters).  The names ride in the compiled
program's op metadata only, and a profiler trace carries them per device op
(its ``tf_op``), so device time can be split by phase.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.hashing import EMPTY_KEY
from repro.core.policies import Policy, on_hit, on_insert, victim_scores

NEG_INF = jnp.float32(-3.0e38)
POS_INF = jnp.float32(3.0e38)

#: "never expires" deadline sentinel (int32 max).  Every lane of a fresh
#: expiry array holds it, so a cache with the lane but no TTL-bearing
#: requests behaves bit-identically to one without the lane.
NO_EXPIRY = 0x7FFFFFFF


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KWayState:
    """Cache contents.  A pytree — shardable, scannable, checkpointable.

    ``expiry`` is the optional TTL lane (DESIGN.md §15): an absolute
    int32 deadline on the replay clock per cached entry, ``NO_EXPIRY``
    when the entry never expires.  ``None`` (the default) means the
    cache has no expiry semantics at all — the pytree then has exactly
    the pre-TTL leaves, so every TTL-disabled code path is bit-identical
    to the lane-less implementation by construction.
    """

    keys: jnp.ndarray    # uint32 [S, k]
    fprint: jnp.ndarray  # uint32 [S, k]
    vals: jnp.ndarray    # int32  [S, k]
    meta_a: jnp.ndarray  # int32  [S, k]
    meta_b: jnp.ndarray  # int32  [S, k]
    clock: jnp.ndarray   # int32  []
    expiry: Optional[jnp.ndarray] = None  # int32 [S, k] | None

    @property
    def num_sets(self) -> int:
        return self.keys.shape[0]

    @property
    def ways(self) -> int:
        return self.keys.shape[1]

    @property
    def capacity(self) -> int:
        return self.keys.size

    def occupancy(self) -> jnp.ndarray:
        return jnp.sum(self.keys != EMPTY_KEY)


@dataclasses.dataclass(frozen=True)
class KWayConfig:
    """Static cache configuration (hashable; safe as a jit static arg)."""

    num_sets: int
    ways: int
    policy: Policy = Policy.LRU
    layout: str = "soa"          # "soa" (KW-WFSC) | "aos" (KW-WFA)
    sample: int = 0              # >0: sampled policy — score only `sample`
    #                              random ways (Redis-style; meaningful for
    #                              the fully-associative configuration)
    seed: int = 0x51CA

    def __post_init__(self):
        assert self.num_sets >= 1 and self.num_sets & (self.num_sets - 1) == 0
        assert self.ways >= 1
        assert self.layout in ("soa", "aos")

    @property
    def capacity(self) -> int:
        return self.num_sets * self.ways


def fully_associative(capacity: int, policy: Policy, sample: int = 0) -> KWayConfig:
    """The paper's baseline: one set spanning the whole cache."""
    return KWayConfig(num_sets=1, ways=capacity, policy=policy, sample=sample)


def make_cache(cfg: KWayConfig, *, ttl: bool = False) -> KWayState:
    s, k = cfg.num_sets, cfg.ways
    return KWayState(
        keys=jnp.full((s, k), EMPTY_KEY, jnp.uint32),
        fprint=jnp.zeros((s, k), jnp.uint32),
        vals=jnp.zeros((s, k), jnp.int32),
        meta_a=jnp.zeros((s, k), jnp.int32),
        meta_b=jnp.zeros((s, k), jnp.int32),
        clock=jnp.zeros((), jnp.int32),
        expiry=(jnp.full((s, k), NO_EXPIRY, jnp.int32) if ttl else None),
    )


def ensure_expiry(state: KWayState) -> KWayState:
    """Attach an all-``NO_EXPIRY`` expiry lane if the state lacks one."""
    if state.expiry is not None:
        return state
    return dataclasses.replace(
        state, expiry=jnp.full(state.keys.shape, NO_EXPIRY, jnp.int32))


@jax.named_scope("kway.scrub")
def scrub_expired(state: KWayState, horizon: jnp.ndarray) -> KWayState:
    """Reclaim every entry whose deadline is at or before ``horizon``.

    The expiry contract (DESIGN.md §15): each batch scrubs with
    ``horizon = clock_at_entry + 2B`` — the clock value at batch *exit* —
    so an entry is visible to a batch only if it is still live when the
    batch retires.  Scrubbed lanes become ordinary empty lanes (never
    hit, filled first by victim selection); reclaiming one is not an
    eviction.  The resulting steady-state invariant, independent of
    batch size, is ``occupied ⇒ expiry > clock`` — what the
    ``expired_resident`` validator bit checks.  No-op when the state has
    no expiry lane.
    """
    if state.expiry is None:
        return state
    dead = (state.keys != EMPTY_KEY) & (state.expiry <= horizon)
    return dataclasses.replace(
        state,
        keys=jnp.where(dead, jnp.uint32(EMPTY_KEY), state.keys),
        fprint=jnp.where(dead, jnp.uint32(0), state.fprint),
        vals=jnp.where(dead, jnp.int32(0), state.vals),
        meta_a=jnp.where(dead, jnp.int32(0), state.meta_a),
        meta_b=jnp.where(dead, jnp.int32(0), state.meta_b),
        expiry=jnp.where(dead, jnp.int32(NO_EXPIRY), state.expiry),
    )


def insert_deadlines(clock, b: int, ttls: Optional[jnp.ndarray]):
    """Deadlines for this batch's inserts: ``clock + 2B + ttl`` (TTL
    counted from the batch-exit clock), ``NO_EXPIRY`` for ``ttl <= 0``.

    The deadline is a *chunk-level* constant plus the per-request TTL —
    deliberately independent of the lane's position inside the batch, so
    the sharded replay (which permutes lanes into owner buckets but
    advances every shard's clock by the same 2B per step) lands
    bit-identical deadlines to the unsharded path.
    """
    if ttls is None:
        return None
    dl = clock + jnp.int32(2 * b) + ttls.astype(jnp.int32)
    return jnp.where(ttls > 0, dl, jnp.int32(NO_EXPIRY))


# ---------------------------------------------------------------------------
# the slot view
#
# Every scatter into a state lane, and every gather of single (set, way)
# slots, goes through a flat [S*k] view of the lane.  On the TPU an [S, k]
# 32-bit lane with 8 ways is carried ways-major in 8x128 tiles
# ({0,1:T(8,128)}), while a scatter writes only a linear 1-D array: a
# scatter into the [S, k] lane itself makes XLA copy the lane to linear form
# and lay it back out, by a loop over the whole lane, after every scatter.
# A view in the tile's own memory order is a bitcast of the carried layout,
# so the lanes are written in place.  A set's whole row ([B, k]: the probe,
# the victims' scores) is still gathered from the [S, k] lane: through the
# view it is B*k single-slot reads, which on a v5e took 15-30 times as long
# as the row gather.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Slots:
    """The flat slot view of ``[num_sets, ways]`` lanes: the bijection
    ``slot(set, way)`` onto ``[0, S*k)`` and the reshapes between a lane
    and its view.

    With 8 ways over a multiple of 128 sets the order is the chip's tile
    order, 128 sets of one way after another:
    ``slot = (set >> 7 << 10) | (way << 7) | (set & 127)``.  Any other
    geometry takes ``set * k + way``: as exact, but the chip then copies
    the lane into and out of the view.  ``drop`` (``S * k``) is one past
    the last slot, where a write is dropped.
    """

    num_sets: int
    ways: int

    @property
    def tiled(self) -> bool:
        return self.ways == 8 and self.num_sets % 128 == 0

    @property
    def drop(self) -> int:
        return self.num_sets * self.ways

    def slot(self, sets, ways):
        """Slot of each (set, way) pair (int32 arrays that broadcast)."""
        if self.tiled:
            return ((sets >> 7) << 10) | (ways << 7) | (sets & 127)
        return sets * jnp.int32(self.ways) + ways

    def flat(self, lane):
        """[S, k] lane -> its [S*k] view."""
        s, k = self.num_sets, self.ways
        if self.tiled:
            return lane.reshape(s // 128, 128, k).transpose(0, 2, 1).reshape(-1)
        return lane.reshape(-1)

    def lane(self, flat):
        """[S*k] view -> its [S, k] lane."""
        s, k = self.num_sets, self.ways
        if self.tiled:
            return flat.reshape(s // 128, k, 128).transpose(0, 2, 1).reshape(s, k)
        return flat.reshape(s, k)


# ---------------------------------------------------------------------------
# probing
# ---------------------------------------------------------------------------

@jax.named_scope("kway.probe")
def _probe(cfg: KWayConfig, state: KWayState, qkeys: jnp.ndarray):
    """Gather each query's set and locate the key.

    Returns (sets[B], set_keys[B,k], hit[B], way[B]).  The SoA layout
    pre-filters with fingerprints (KW-WFSC Algorithm 5); AoS compares full
    keys directly (KW-WFA Algorithm 2).  Both produce identical results —
    fingerprints are a scan accelerator, never a correctness shortcut: a
    fingerprint match is confirmed against the full key.
    """
    qkeys = hashing.sanitize_keys(qkeys)
    sets = hashing.set_index(qkeys, cfg.num_sets, cfg.seed)
    set_keys = state.keys[sets]                      # [B, k] gather
    if cfg.layout == "soa":
        qfp = hashing.fingerprint(qkeys)[:, None]
        cand = state.fprint[sets] == qfp             # cheap contiguous scan
        eq = cand & (set_keys == qkeys[:, None])     # confirm on full key
    else:
        eq = set_keys == qkeys[:, None]
    eq = eq & (set_keys != EMPTY_KEY)
    hit = jnp.any(eq, axis=-1)
    way = jnp.argmax(eq, axis=-1).astype(jnp.int32)
    return qkeys, sets, set_keys, hit, way


def _batch_times(state: KWayState, b: int):
    """Per-request logical timestamps: batch order == arrival order."""
    times = state.clock + jnp.arange(b, dtype=jnp.int32)
    return times, state.clock + jnp.int32(b)


def sampled_way_ids(sample: int, ways: int, times: jnp.ndarray) -> jnp.ndarray:
    """Pseudo-random way ids (with replacement) for sampled victim selection
    (Redis-style, O(sample)).  ``times`` int32 [...] -> int32 [..., sample].
    The single source of truth for the draw scheme — the sweep runner
    (repro/eval/runner.py) replays it bit-for-bit."""
    draw = jnp.arange(sample, dtype=jnp.uint32)
    h = hashing.hash_u32(
        draw + times[..., None].astype(jnp.uint32) * jnp.uint32(2654435761),
        seed=0x5A5A,
    )
    return (h % jnp.uint32(ways)).astype(jnp.int32)


@jax.named_scope("kway.victims")
def _victim_order_arrays(cfg: KWayConfig, keys_arr, meta_a_arr, meta_b_arr,
                         sets, set_keys, times):
    """Per request: ways of its set ordered worst-victim-first. [B, k]
    (or [B, sample] for sampled policies — see below).  Takes the state
    lanes as plain arrays so the fused access path can score on the
    hit-updated metadata without materialising an intermediate state."""
    if cfg.sample > 0 and cfg.sample < cfg.ways:
        # Sampled policy: draw `sample` ways (with replacement), score only
        # those.
        m = cfg.sample
        way_ids = sampled_way_ids(m, cfg.ways, times)               # [B, m]
        ma = meta_a_arr[sets[:, None], way_ids]
        mb = meta_b_arr[sets[:, None], way_ids]
        keys_s = keys_arr[sets[:, None], way_ids]
        scores = victim_scores(cfg.policy, ma, mb, times[:, None], keys_s)
        scores = jnp.where(keys_s == EMPTY_KEY, NEG_INF, scores)
        order_local = jnp.argsort(scores, axis=-1)
        return jnp.take_along_axis(way_ids, order_local, axis=-1)   # [B, m]
    ma = meta_a_arr[sets]
    mb = meta_b_arr[sets]
    scores = victim_scores(cfg.policy, ma, mb, times[:, None], set_keys)
    empty = set_keys == EMPTY_KEY
    scores = jnp.where(empty, NEG_INF, scores)  # fill empty ways first
    return jnp.argsort(scores, axis=-1).astype(jnp.int32)  # [B, k]


def _victim_order(cfg: KWayConfig, state: KWayState, sets, set_keys, times):
    return _victim_order_arrays(cfg, state.keys, state.meta_a, state.meta_b,
                                sets, set_keys, times)


@jax.named_scope("kway.resolve")
def _resolve_inserts(cfg: KWayConfig, qkeys, sets, eligible, order):
    """Deterministic insert conflict resolution, shared by ``apply_put`` and
    ``apply_access`` (one definition so the fused and two-phase paths cannot
    drift): dedupe duplicate keys within the batch, rank same-set collisions
    by arrival order, cap at k admits per set, and pick each insert's victim
    way from ``order`` ([B, m], worst-victim-first).

    The vectorized stand-in for the paper's CAS retry loop, in three sorts
    that carry their payloads (no data-dependent gather or scatter: on the
    TPU one over a batch's lanes costs several sorts of it):

      1. by (key, arrival), carrying each lane's set (a sentinel past every
         real set if ineligible): the first eligible lane of each equal-key
         run is the one that inserts;
      2. by (set, arrival) over the inserts, the rest under the sentinel: a
         segmented scan of group starts gives
         rank[i] = #(earlier inserts into i's set);
      3. by arrival, carrying the ordinal rank + 1 (0 for lanes that do not
         insert) back into batch order.

    Each sort's keys are unique (arrival breaks ties), so none needs to be
    stable.  Ineligible lanes sort under ``EMPTY_KEY``, which
    ``sanitize_keys`` guarantees is never a real key — a valid-key sentinel
    (e.g. 0) would absorb the first occurrence of that key whenever an
    ineligible lane precedes it.

    Returns (is_insert bool[B], way_victim int32[B]); way_victim is the
    rank-selected way for every lane (callers mask with is_insert).
    """
    b = qkeys.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    no_set = jnp.int32(0x7FFFFFFF)
    key1 = jnp.where(eligible, qkeys, EMPTY_KEY).astype(jnp.uint32)
    set1 = jnp.where(eligible, sets.astype(jnp.int32), no_set)
    key1, arr1, set1 = jax.lax.sort((key1, idx, set1), num_keys=2,
                                    is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), key1[1:] != key1[:-1]])

    key2 = jnp.where(first, set1, no_set)
    key2, arr2 = jax.lax.sort((key2, arr1), num_keys=2, is_stable=False)
    new_group = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), key2[1:] != key2[:-1]])
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_group, idx, 0))
    ordinal = jnp.where(key2 != no_set, idx - group_start + 1, 0)

    _, ordinal = jax.lax.sort((arr2, ordinal), num_keys=1, is_stable=False)
    is_insert = (ordinal > 0) & (ordinal <= cfg.ways)     # ≤ k admits per set
    # One-hot select of column rank = ordinal - 1 (lanes that do not insert:
    # column 0; past a sampled order's end: its last column).
    rank_c = jnp.clip(ordinal - 1, 0, order.shape[1] - 1)
    cols = jnp.arange(order.shape[1], dtype=jnp.int32)
    way_victim = jnp.sum(
        jnp.where(cols[None, :] == rank_c[:, None], order, 0), axis=-1)
    return is_insert, way_victim.astype(order.dtype)


# ---------------------------------------------------------------------------
# decision application (shared by every probe implementation)
#
# Probing (locate the key / rank the victims) and applying (scatter the new
# contents) are split so alternative probe substrates — the pure-jnp path
# below, the Pallas kernel in kernels/kway_probe.py — feed one common apply
# and stay bit-identical (DESIGN.md §3).  The applies write the lanes by two
# helpers on slot views, one per phase, so the three cannot drift.
# ---------------------------------------------------------------------------

def _write_hits(cfg: KWayConfig, meta_a, meta_b, slot, hit, times):
    """The hit phase's lane write: each hit's ``on_hit`` transition of
    ``meta_a`` at its ``slot`` (slot views in, ``meta_a``'s view out).

    Duplicate slots in one batch: LFU/Hyperbolic counts accumulate (two
    hits = +2, a scatter-add of the deltas), LRU takes the latest stamp (a
    scatter-max).  ``on_hit`` is the identity for FIFO/RANDOM and keeps
    ``meta_b`` for every policy, so neither is ever written.
    """
    if cfg.policy in (Policy.FIFO, Policy.RANDOM):
        return meta_a
    ma_hit = meta_a[slot]
    new_a, _ = on_hit(cfg.policy, ma_hit, meta_b[slot], times)
    if cfg.policy in (Policy.LFU, Policy.HYPERBOLIC):
        return meta_a.at[slot].add(jnp.where(hit, new_a - ma_hit, 0),
                                   mode="drop")
    return meta_a.at[slot].max(jnp.where(hit, new_a, -(2**31 - 1)),
                               mode="drop")


def _write_entries(v: _Slots, state: KWayState, meta_a, slot_w, qkeys, qvals,
                   new_a, new_b, deadline, clock) -> KWayState:
    """The insert phase's lane writes: one packed scatter pass, a single
    slot index per request shared by every lane of ``state`` (``meta_a``
    given as the slot view the hit phase left).  Requests that land nowhere
    carry ``v.drop``, past the last slot, where the write is dropped — a
    parking slot such as 0 is not a no-op, since a duplicate index lets the
    parked write clobber an active request's insert there.  Returns the
    state with its lanes back in ``[S, k]``."""
    def put(flat, x):
        return v.lane(flat.at[slot_w].set(x, mode="drop"))

    return KWayState(
        keys=put(v.flat(state.keys), qkeys),
        fprint=put(v.flat(state.fprint), hashing.fingerprint(qkeys)),
        vals=put(v.flat(state.vals), qvals),
        meta_a=put(meta_a, new_a),
        meta_b=put(v.flat(state.meta_b), new_b),
        clock=clock,
        expiry=(None if state.expiry is None
                else put(v.flat(state.expiry), deadline)),
    )


@partial(jax.jit, static_argnums=0)
def apply_get(cfg: KWayConfig, state: KWayState, sets, hit, way):
    """Apply read-side policy-metadata updates for already-probed queries.

    Returns (state', hit[B], vals[B]).
    """
    b = sets.shape[0]
    times, clock = _batch_times(state, b)
    v = _Slots(*state.keys.shape)

    with jax.named_scope("kway.hit"):
        slot = v.slot(sets, way)
        meta_a = _write_hits(cfg, v.flat(state.meta_a), v.flat(state.meta_b),
                             slot, hit, times)
        vals = jnp.where(hit, v.flat(state.vals)[slot], -1)
    return (
        dataclasses.replace(state, meta_a=v.lane(meta_a), clock=clock),
        hit,
        vals,
    )


@partial(jax.jit, static_argnums=0, static_argnames=("slot_value",))
def apply_put(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    qvals: jnp.ndarray,
    sets: jnp.ndarray,
    present: jnp.ndarray,
    way_present: jnp.ndarray,
    order: jnp.ndarray,
    admit: Optional[jnp.ndarray] = None,
    enabled: Optional[jnp.ndarray] = None,
    *,
    slot_value: bool = False,
):
    """Apply write decisions: deterministic conflict resolution + one scatter.

    ``order`` is [B, m]: per request, the ways of its set worst-victim-first
    (m == ways, or the sample size for sampled policies).  ``slot_value``
    stores ``set * ways + way`` — the landing slot id — as the payload
    instead of ``qvals`` (the paged-KV engine's page-id convention).

    Returns (state', evicted_keys[B], evicted_valid[B], slot_sets[B],
    slot_ways[B]); slot_* are -1 for lanes that did not land (not admitted,
    intra-batch duplicate, per-set overflow, or disabled).
    """
    b = qkeys.shape[0]
    times, clock = _batch_times(state, b)
    if admit is None:
        admit = jnp.ones((b,), jnp.bool_)
    if enabled is None:
        enabled = jnp.ones((b,), jnp.bool_)
    present = present & enabled

    is_insert, way_victim = _resolve_inserts(
        cfg, qkeys, sets, (~present) & admit & enabled, order)

    v = _Slots(*state.keys.shape)
    with jax.named_scope("kway.insert"):
        way = jnp.where(present, way_present, way_victim)
        active = present | is_insert

        evicted_keys = v.flat(state.keys)[v.slot(sets, way_victim)]
        evicted_valid = is_insert & (evicted_keys != EMPTY_KEY)

        ia, ib = on_insert(cfg.policy, times, (b,))

        # For present keys: overwrite value, metadata takes the on_hit
        # transition (a put of an existing key counts as an access — paper
        # Algorithm 3 line 6).
        slot = v.slot(sets, way)
        meta_a = v.flat(state.meta_a)
        ha, hb = on_hit(cfg.policy, meta_a[slot], v.flat(state.meta_b)[slot],
                        times)
        new_a = jnp.where(present, ha, ia)
        new_b = jnp.where(present, hb, ib)

        if slot_value:
            qvals = (sets * jnp.int32(cfg.ways) + way).astype(jnp.int32)

        # put has no TTL argument (TTL riding is the fused access path's
        # job); an expiry lane, when present, is carried with landing lanes
        # marked never-expiring so the structural invariants stay intact.
        new_state = _write_entries(
            v, state, meta_a, jnp.where(active, slot, v.drop), qkeys, qvals,
            new_a, new_b, jnp.int32(NO_EXPIRY), clock)

    slot_sets = jnp.where(active, sets, -1)
    slot_ways = jnp.where(active, way, -1)
    return new_state, evicted_keys, evicted_valid, slot_sets, slot_ways


# ---------------------------------------------------------------------------
# public operations
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=0)
def get(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    enabled: Optional[jnp.ndarray] = None,
):
    """Batched read (paper Algorithm 2/5/8).

    Returns (state', hit[B] bool, vals[B] int32).  Hits update policy
    metadata; misses leave the cache untouched.  ``enabled`` (bool[B],
    optional) masks whole lanes (they still consume a logical timestamp —
    used by the sharded layer's padding lanes).
    """
    qkeys, sets, set_keys, hit, way = _probe(cfg, state, qkeys)
    if enabled is not None:
        hit = hit & enabled
    return apply_get(cfg, state, sets, hit, way)


@partial(jax.jit, static_argnums=0, static_argnames=("slot_value",))
def put(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    qvals: jnp.ndarray,
    admit: Optional[jnp.ndarray] = None,
    enabled: Optional[jnp.ndarray] = None,
    *,
    slot_value: bool = False,
):
    """Batched write (paper Algorithm 3/6/9).

    Present keys are overwritten in place; absent keys evict a policy victim
    from their own set.  ``admit`` (bool[B], optional) gates admission of
    absent keys — the hook the TinyLFU filter plugs into.  ``enabled``
    (bool[B], optional) disables whole lanes (used by ``access`` so a lane
    that already hit in the read phase is not written twice).

    Returns (state', evicted_keys uint32[B], evicted_valid bool[B],
    slot_sets int32[B], slot_ways int32[B]).  The evicted keys let callers
    (e.g. the paged-KV allocator) recycle the victims' payloads; the slot
    arrays report where each key landed (-1 when it did not land).
    """
    qkeys, sets, set_keys, present, way_present = _probe(cfg, state, qkeys)
    times, _ = _batch_times(state, qkeys.shape[0])
    order = _victim_order(cfg, state, sets, set_keys, times)
    return apply_put(
        cfg, state, qkeys, qvals, sets, present, way_present, order,
        admit, enabled, slot_value=slot_value,
    )


@partial(jax.jit, static_argnums=0, static_argnames=("slot_value",))
def apply_access(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    qvals: jnp.ndarray,
    sets: jnp.ndarray,
    hit_raw: jnp.ndarray,
    way: jnp.ndarray,
    admit: Optional[jnp.ndarray] = None,
    enabled: Optional[jnp.ndarray] = None,
    order: Optional[jnp.ndarray] = None,
    set_keys: Optional[jnp.ndarray] = None,
    ttls: Optional[jnp.ndarray] = None,
    *,
    slot_value: bool = False,
):
    """Fused one-pass apply for ``access`` — one probe feeds both phases.

    Consumes one probe's decisions (``hit_raw``/``way``, *unmasked* by
    ``enabled``) and applies the get-then-put-on-miss composition in a single
    pass, bit-identical to ``apply_get`` followed by ``apply_put`` (DESIGN.md
    §8).  Two-phase clock accounting is preserved: hits stamp ``t+i``,
    inserts stamp ``t+B+i``, and the clock advances by 2B.  Victim scores are
    computed on the *post-hit-update* metadata (``meta_a1``), exactly what
    the second probe of the two-phase path would observe — the keys lanes are
    untouched by the hit phase, so the probe itself never needs repeating.

    ``order`` (int32 [B, m], worst-victim-first) can be supplied by a caller
    that already derived it from the same post-hit metadata (the fused Pallas
    kernel); otherwise it is computed here from ``set_keys`` (the [B, k]
    gather of the first probe).  Exactly one of the two must be given.

    Scatter economy vs the two-phase applies (7 scatters per step): the hit
    phase scatters only ``meta_a`` (``on_hit`` keeps ``meta_b`` for every
    policy, and is the identity for FIFO/RANDOM), and the insert phase is
    one packed scatter pass — a single slot index shared by all five state
    lanes (six with the expiry lane).  Every gather and scatter reads or
    writes a lane through its flat slot view (``_Slots``), in the chip's
    tile order where the geometry allows, so on the TPU a lane is written
    in place rather than laid out anew after each scatter.

    ``slot_value`` is the cache-as-allocator mode (the paged-KV engine's
    page-id convention): inserts store ``set * ways + way`` — the landing
    slot id — as the payload, and ``vals`` returns the hit lane's stored
    slot id, the insert lane's fresh slot id, or -1 where the key did not
    land (not admitted / duplicate / per-set overflow / disabled).  One
    fused call answers "which page holds this block, allocating if absent"
    for a whole batch — bit-identical to the get + slot-returning-put
    composition (``CacheBackend.access_two_phase`` with ``slot_value``).

    ``ttls`` (int32 [B], optional) gives each request a time-to-live on
    the logical clock: its insert lands with deadline ``clock + 2B + ttl``
    (``NO_EXPIRY`` for ``ttl <= 0``); hits never refresh a deadline.  The
    caller is responsible for having scrubbed expired entries at batch
    entry (``scrub_expired`` with the batch-exit horizon) — the probe
    feeding this apply then cannot see an expired key.  Requires the
    state to carry an expiry lane.

    Returns (state', hit[B], vals[B], evicted_keys[B], evicted_valid[B]).
    """
    if ttls is not None and state.expiry is None:
        raise ValueError(
            "apply_access: ttls given but the state has no expiry lane — "
            "build it with make_cache(cfg, ttl=True) or ensure_expiry()")
    b = qkeys.shape[0]
    times_get = state.clock + jnp.arange(b, dtype=jnp.int32)
    times_put = times_get + jnp.int32(b)
    clock = state.clock + jnp.int32(2 * b)

    hit = hit_raw if enabled is None else (hit_raw & enabled)
    v = _Slots(*state.keys.shape)
    keys_f, meta_b_f, vals_f = (v.flat(state.keys), v.flat(state.meta_b),
                                v.flat(state.vals))

    # ---- hit phase (apply_get semantics at times t+i) --------------------
    with jax.named_scope("kway.hit"):
        slot_hit = v.slot(sets, way)
        meta_a1 = _write_hits(cfg, v.flat(state.meta_a), meta_b_f, slot_hit,
                              hit, times_get)
        vals_out = jnp.where(hit, vals_f[slot_hit], qvals)

    # ---- miss phase (apply_put semantics at times t+B+i) -----------------
    # In the composition, every lane the put phase sees is either disabled
    # (it hit in the get phase) or absent, so the present/overwrite branch of
    # apply_put never fires: the put phase is pure insert resolution.
    if admit is None:
        admit = jnp.ones((b,), jnp.bool_)
    if enabled is None:
        enabled = jnp.ones((b,), jnp.bool_)
    if order is None:
        order = _victim_order_arrays(cfg, state.keys, v.lane(meta_a1),
                                     state.meta_b, sets, set_keys, times_put)

    is_insert, way_victim = _resolve_inserts(
        cfg, qkeys, sets, (~hit_raw) & admit & enabled, order)

    with jax.named_scope("kway.insert"):
        slot_ins = v.slot(sets, way_victim)
        evicted_keys = keys_f[slot_ins]
        evicted_valid = is_insert & (evicted_keys != EMPTY_KEY)

        if slot_value:
            slot_id = (sets * jnp.int32(cfg.ways) + way_victim).astype(jnp.int32)
            qvals = slot_id                      # stored payload for inserts
            vals_out = jnp.where(
                hit, vals_f[slot_hit],
                jnp.where(is_insert, slot_id, jnp.int32(-1)))

        ia, ib = on_insert(cfg.policy, times_put, (b,))
        ie = insert_deadlines(state.clock, b, ttls)
        if ie is None:           # lane present, no TTLs: never-expiring
            ie = jnp.int32(NO_EXPIRY)
        new_state = _write_entries(
            v, state, meta_a1, jnp.where(is_insert, slot_ins, v.drop), qkeys,
            qvals, ia, ib, ie, clock)

    return new_state, hit, vals_out, evicted_keys, evicted_valid


def _access_fused(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    qvals: jnp.ndarray,
    admit_on_miss: Optional[jnp.ndarray] = None,
    enabled: Optional[jnp.ndarray] = None,
    ttls: Optional[jnp.ndarray] = None,
    *,
    slot_value: bool = False,
):
    # Expiry scrub precedes the probe (the "never serve stale" hard
    # guarantee): an expired key is reclaimed before any hit decision is
    # made, so the probe itself needs no expiry awareness.
    if state.expiry is not None:
        b = qkeys.shape[0]
        state = scrub_expired(state, state.clock + jnp.int32(2 * b))
    qkeys, sets, set_keys, hit_raw, way = _probe(cfg, state, qkeys)
    return apply_access(cfg, state, qkeys, qvals, sets, hit_raw, way,
                        admit_on_miss, enabled, set_keys=set_keys,
                        ttls=ttls, slot_value=slot_value)


#: The canonical cache loop: get; on miss, put (paper §5.1.2 methodology) —
#: fused single-probe form.  Returns (state', hit[B], vals[B],
#: evicted_keys[B], evicted_valid[B]); bit-identical to ``access_two_phase``.
access = partial(jax.jit, static_argnums=0,
                 static_argnames=("slot_value",))(_access_fused)

#: Buffer-donating variant of ``access``: the input ``state`` buffers are
#: donated to XLA so ``KWayState`` is updated in place (5 S×k arrays are not
#: copied every batch).  The caller must not reuse ``state`` afterwards.
#: Backends without donation support (CPU on older jaxlibs) fall back to a
#: copy with a one-time warning.
access_donated = partial(
    jax.jit, static_argnums=0, donate_argnums=1,
    static_argnames=("slot_value",))(_access_fused)


@partial(jax.jit, static_argnums=0, static_argnames=("slot_value",))
def access_two_phase(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    qvals: jnp.ndarray,
    admit_on_miss: Optional[jnp.ndarray] = None,
    enabled: Optional[jnp.ndarray] = None,
    *,
    slot_value: bool = False,
):
    """The unfused get-then-put composition — two probes, two apply passes.

    Kept as the differential oracle for ``access``: tests assert the fused
    path is bit-identical to this one (hits, evictions, final state) — with
    ``slot_value``, also the returned page/slot ids.
    """
    state, hit, vals = get(cfg, state, qkeys, enabled=enabled)
    admit = admit_on_miss if admit_on_miss is not None else None
    en = (~hit) if enabled is None else (enabled & ~hit)
    state, ek, ev, ss, sw = put(cfg, state, qkeys, qvals, admit=admit,
                                enabled=en, slot_value=slot_value)
    if slot_value:
        landed = ss >= 0
        slot_id = ss * jnp.int32(cfg.ways) + sw
        vals = jnp.where(hit, vals, jnp.where(landed, slot_id, -1))
    else:
        vals = jnp.where(hit, vals, qvals)
    return state, hit, vals, ek, ev


@partial(jax.jit, static_argnums=0)
def peek_victims(cfg: KWayConfig, state: KWayState, qkeys: jnp.ndarray):
    """Prospective victim key for each query, without mutating the cache.

    Used by admission filters (TinyLFU): the candidate competes against the
    key it *would* evict.  Returns (victim_keys uint32[B], victim_valid
    bool[B]); victim_valid is False when the set has a free way (admission is
    then unconditional) or the key is already present (no eviction).
    """
    qkeys2, sets, set_keys, present, _ = _probe(cfg, state, qkeys)
    times, _ = _batch_times(state, qkeys.shape[0])
    order = _victim_order(cfg, state, sets, set_keys, times)
    way0 = order[:, 0]
    vkeys = state.keys[sets, way0]
    valid = (vkeys != EMPTY_KEY) & (~present)
    return vkeys, valid


# ---------------------------------------------------------------------------
# AoS record packing (KW-WFA layout baseline)
# ---------------------------------------------------------------------------

def pack_aos(state: KWayState) -> jnp.ndarray:
    """Interleave the SoA lanes into one [S, k, 4] record array (int32).

    KW-WFA stores a node per way; gathering a record touches 4 interleaved
    words.  The throughput benchmark contrasts this with the SoA layout to
    reproduce the paper's KW-WFA vs KW-WFSC comparison on vector hardware.
    """
    return jnp.stack(
        [
            state.keys.astype(jnp.int32),
            state.vals,
            state.meta_a,
            state.meta_b,
        ],
        axis=-1,
    )


def unpack_aos(rec: jnp.ndarray, clock: jnp.ndarray) -> KWayState:
    keys = rec[..., 0].astype(jnp.uint32)
    return KWayState(
        keys=keys,
        fprint=hashing.fingerprint(keys),
        vals=rec[..., 1],
        meta_a=rec[..., 2],
        meta_b=rec[..., 3],
        clock=clock,
    )
