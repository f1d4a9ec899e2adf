"""K-way cache unit + oracle-agreement tests (the paper's core).

Hypothesis property tests live in tests/test_kway_properties.py, which
skips itself when `hypothesis` is not installed (see requirements-dev.txt).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashing, kway
from repro.core.backend import make_backend
from repro.core.hashing import EMPTY_KEY
from repro.core.kway import KWayConfig, fully_associative
from repro.core.policies import Policy
from repro.core.refimpl import RefKWay

POLICIES = [Policy.LRU, Policy.LFU, Policy.FIFO, Policy.RANDOM, Policy.HYPERBOLIC]


def _run_trace(cfg, trace):
    st_ = kway.make_cache(cfg)
    hits = []
    for t in trace:
        st_, h, v, ek, ev = kway.access(
            cfg, st_, jnp.array([t], jnp.uint32), jnp.array([int(t)], jnp.int32)
        )
        hits.append(bool(h[0]))
    return st_, hits


@pytest.mark.parametrize("policy", POLICIES)
def test_exact_oracle_agreement(policy, rng):
    """JAX cache at B=1 == serial transcription of the paper's algorithms."""
    trace = rng.integers(0, 150, size=600, dtype=np.uint32)
    cfg = KWayConfig(num_sets=8, ways=4, policy=policy)
    ref = RefKWay(8, 4, policy)
    st_ = kway.make_cache(cfg)
    for t in trace:
        st_, h, _, _, _ = kway.access(
            cfg, st_, jnp.array([t], jnp.uint32), jnp.array([int(t)], jnp.int32)
        )
        rh = ref.access(int(t), int(t))
        assert bool(h[0]) == rh
    jax_keys = {int(x) for x in np.asarray(st_.keys).ravel() if x != 0xFFFFFFFF}
    assert jax_keys == ref.contents()


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("layout", ["soa", "aos"])
def test_capacity_never_exceeded(policy, layout, rng):
    cfg = KWayConfig(num_sets=4, ways=4, policy=policy, layout=layout)
    st_, _ = _run_trace(cfg, rng.integers(0, 1000, 300, dtype=np.uint32))
    assert int(st_.occupancy()) <= cfg.capacity
    # no key stored twice
    keys = [int(x) for x in np.asarray(st_.keys).ravel() if x != 0xFFFFFFFF]
    assert len(keys) == len(set(keys))


def test_hit_implies_present(rng):
    cfg = KWayConfig(num_sets=8, ways=4, policy=Policy.LRU)
    st_ = kway.make_cache(cfg)
    seen = set()
    for t in rng.integers(0, 100, 400, dtype=np.uint32):
        st_, h, v, _, _ = kway.access(
            cfg, st_, jnp.array([t], jnp.uint32), jnp.array([int(t)], jnp.int32)
        )
        if bool(h[0]):
            assert int(t) in seen
            assert int(v[0]) == int(t)  # value integrity
        seen.add(int(t))


def test_fully_associative_is_one_set():
    cfg = fully_associative(16, Policy.LRU)
    assert cfg.num_sets == 1 and cfg.ways == 16
    st_, hits = _run_trace(cfg, np.arange(16, dtype=np.uint32))
    assert int(st_.occupancy()) == 16
    # LRU eviction order: access 16 (evicts 0), then 0 must miss
    st_, h, _, _, _ = kway.access(cfg, st_, jnp.array([16], jnp.uint32),
                                  jnp.array([16], jnp.int32))
    assert not bool(h[0])
    st_, h, _, _, _ = kway.access(cfg, st_, jnp.array([0], jnp.uint32),
                                  jnp.array([0], jnp.int32))
    assert not bool(h[0])  # 0 was the LRU victim


def test_batched_matches_serial_when_sets_distinct(rng):
    """The paper's embarrassing parallelism: requests to different sets
    commute — a batched step equals any serialization."""
    cfg = KWayConfig(num_sets=64, ways=4, policy=Policy.LFU)
    # distinct sets: pick keys with distinct set indices
    keys, seen = [], set()
    k = 0
    while len(keys) < 16:
        s = int(hashing.set_index(jnp.array([k], jnp.uint32), 64)[0])
        if s not in seen:
            seen.add(s)
            keys.append(k)
        k += 1
    keys = np.array(keys, np.uint32)

    st_b = kway.make_cache(cfg)
    st_b, hb, _, _, _ = kway.access(cfg, st_b, jnp.asarray(keys),
                                    jnp.asarray(keys.astype(np.int32)))
    st_s = kway.make_cache(cfg)
    for t in keys:
        st_s, _, _, _, _ = kway.access(
            cfg, st_s, jnp.array([t], jnp.uint32), jnp.array([int(t)], jnp.int32)
        )
    jb = {int(x) for x in np.asarray(st_b.keys).ravel() if x != 0xFFFFFFFF}
    js = {int(x) for x in np.asarray(st_s.keys).ravel() if x != 0xFFFFFFFF}
    assert jb == js


def test_batched_conflict_bounded_and_deduped(rng):
    """Same-set collisions: ≤ k admissions per set per batch; duplicate keys
    admitted once (documented CAS-race semantics)."""
    cfg = KWayConfig(num_sets=2, ways=4, policy=Policy.LRU)
    st_ = kway.make_cache(cfg)
    keys = np.array([1, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], np.uint32)
    st_, _, _, _, _ = kway.access(cfg, st_, jnp.asarray(keys),
                                  jnp.asarray(keys.astype(np.int32)))
    assert int(st_.occupancy()) <= cfg.capacity
    stored = [int(x) for x in np.asarray(st_.keys).ravel() if x != 0xFFFFFFFF]
    assert len(stored) == len(set(stored))


def test_evicted_keys_reported(rng):
    cfg = KWayConfig(num_sets=1, ways=2, policy=Policy.FIFO)
    st_ = kway.make_cache(cfg)
    for k in [1, 2, 3]:
        st_, _, _, ek, ev = kway.access(
            cfg, st_, jnp.array([k], jnp.uint32), jnp.array([k], jnp.int32)
        )
    assert bool(ev[0]) and int(ek[0]) == 1  # FIFO: 1 evicted by 3


def _full_state(cfg, rng, ttl):
    """A cache with about 90 % of its slots filled with keys of their own
    set under distinct LRU stamps, the rest empty, and the keys of each set
    left over for misses."""
    s, k = cfg.num_sets, cfg.ways
    cand = np.arange(1, 48 * s * k + 1, dtype=np.uint32)
    cset = np.asarray(hashing.set_index(jnp.asarray(cand), s, cfg.seed))
    by_set = [cand[cset == i] for i in range(s)]
    keys = np.full((s, k), EMPTY_KEY, np.uint32)
    for i in range(s):
        keys[i] = by_set[i][:k]
    keys[rng.random((s, k)) < 0.1] = EMPTY_KEY
    occ = keys != EMPTY_KEY
    stamps = rng.permutation(s * k).reshape(s, k).astype(np.int32)
    clock = s * k
    exp = np.where(rng.random((s, k)) < 0.5, kway.NO_EXPIRY,
                   clock + rng.integers(0, 64, (s, k))).astype(np.int32)
    st = kway.KWayState(
        keys=jnp.asarray(keys),
        fprint=jnp.where(occ, hashing.fingerprint(jnp.asarray(keys)),
                         jnp.uint32(0)),
        vals=jnp.asarray(np.where(occ, keys.astype(np.int32), 0)),
        meta_a=jnp.asarray(np.where(occ, stamps, 0)),
        meta_b=jnp.zeros((s, k), jnp.int32),
        clock=jnp.asarray(clock, jnp.int32),
        expiry=jnp.asarray(np.where(occ, exp, kway.NO_EXPIRY)) if ttl else None)
    return st, [list(by_set[i][k:]) for i in range(s)]


def _batch(st, spare, rng, b=16):
    """One request a set at most among the enabled lanes, each a resident
    key or a spare key of its set, so the batched ops equal the oracle's
    serial order; the other lanes are disabled and carry arbitrary keys."""
    s, k = st.keys.shape
    keys = rng.integers(0, 2**32 - 1, b, dtype=np.uint32)
    resident = np.asarray(st.keys)
    enabled = np.zeros(b, bool)
    for lane, set_ in enumerate(rng.permutation(s)[:b]):
        row = resident[set_][resident[set_] != EMPTY_KEY]
        if len(row) and rng.random() < 0.4:
            keys[lane] = rng.choice(row)
        else:
            keys[lane] = spare[set_].pop()
        enabled[lane] = rng.random() < 0.8
    return jnp.asarray(keys), jnp.asarray(enabled)


def _assert_same(got, want, ek=None):
    """Equal outputs; ``ek`` is the position of the evicted keys, which
    are compared where the eviction flag that follows them is set."""
    got, want = list(got), list(want)
    if ek is not None:
        for out in (got, want):
            out[ek] = jnp.where(out[ek + 1], out[ek], 0)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("ways", [1, 4, 8, 16])
@pytest.mark.parametrize("sets", [1, 64, 128, 1024])
def test_slot_view(sets, ways, rng):
    """The private slot view every lane gather and scatter goes through:
    a bijection onto [0, S*k) that indexes element [set, way] of the lane's
    flat view (the chip's tile order at 8 ways over a multiple of 128
    sets), a view that round-trips, and access/get/put through it equal to
    the serial oracle on state and outputs, slot payloads and a TTL lane
    included, with inactive lanes written nowhere."""
    v = kway._Slots(sets, ways)
    set_ids, way_ids = np.meshgrid(np.arange(sets), np.arange(ways),
                                   indexing="ij")
    slots = np.asarray(v.slot(jnp.asarray(set_ids), jnp.asarray(way_ids)))
    np.testing.assert_array_equal(np.sort(slots.ravel()),
                                  np.arange(sets * ways))
    if ways == 8 and sets % 128 == 0:
        np.testing.assert_array_equal(
            slots, (set_ids >> 7 << 10) | (way_ids << 7) | (set_ids & 127))
    lane = jnp.asarray(np.random.default_rng(1).integers(
        0, 2**31, (sets, ways), dtype=np.int32))
    flat = np.asarray(v.flat(lane))
    np.testing.assert_array_equal(flat[slots], np.asarray(lane))
    np.testing.assert_array_equal(np.asarray(v.lane(jnp.asarray(flat))),
                                  np.asarray(lane))

    cfg = KWayConfig(num_sets=sets, ways=ways, policy=Policy.LRU)
    jb, rb = make_backend("jnp", cfg), make_backend("ref", cfg)
    for ttl in (False, True):
        st, spare = _full_state(cfg, rng, ttl)
        for _ in range(2):
            q, en = _batch(st, spare, rng)
            qv = q.astype(jnp.int32) ^ 0x5A5A
            admit = jnp.asarray(rng.random(q.shape[0]) < 0.7)
            _assert_same(jb.get(st, q, en), rb.get(st, q, en))
            for sv in (False, True):
                _assert_same(jb.put(st, q, qv, admit, en, slot_value=sv),
                             rb.put(st, q, qv, admit, en, slot_value=sv), ek=1)
            ttls = (jnp.asarray(rng.integers(-4, 64, q.shape[0]), jnp.int32)
                    if ttl else None)
            got = jb.access(st, q, qv, admit, en, ttls, slot_value=ttl)
            _assert_same(got, rb.access(st, q, qv, admit, en, ttls,
                                        slot_value=ttl), ek=3)
            st = got[0]
        # a batch whose lanes are all inactive writes no slot, the first
        # and the last included (a parked write would land there); access
        # only reclaims what expires by the batch's end
        q, _ = _batch(st, spare, rng)
        off = jnp.zeros(q.shape[0], bool)
        scrubbed = kway.scrub_expired(st, st.clock + 2 * q.shape[0])
        for out, before in (
                (jb.access(st, q, qv, admit, off, ttls, slot_value=ttl)[0],
                 scrubbed),
                (jb.put(st, q, qv, admit, off)[0], st)):
            for a, b in zip(jax.tree_util.tree_leaves(before),
                            jax.tree_util.tree_leaves(out), strict=True):
                if a.ndim == 2:
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
