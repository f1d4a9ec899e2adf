"""TinyLFU admission + trace-replay behaviour (paper §5.2 machinery)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admission, traces
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig, fully_associative
from repro.core.policies import Policy
from repro.core.simulate import SimConfig, replay, replay_batched


def test_sketch_estimates_monotone():
    cfg = admission.TinyLFUConfig(width=256, door_bits=512, sample=100_000)
    st = admission.make_sketch(cfg)
    key = jnp.array([42], jnp.uint32)
    prev = 0
    for i in range(10):
        st = admission.record(cfg, st, key)
        est = int(admission.estimate(cfg, st, key)[0])
        assert est >= prev
        prev = est
    assert prev >= 5  # doorkeeper + sketch count several of the 10


def test_sketch_aging_halves():
    cfg = admission.TinyLFUConfig(width=64, door_bits=128, sample=16)
    st = admission.make_sketch(cfg)
    key = jnp.array([7], jnp.uint32)
    for _ in range(10):
        st = admission.record(cfg, st, key)
    before = int(admission.estimate(cfg, st, key)[0])
    # trigger aging with other keys
    other = jnp.arange(100, 120, dtype=jnp.uint32)
    st = admission.record(cfg, st, other)
    after = int(admission.estimate(cfg, st, key)[0])
    assert after < before


def test_admit_prefers_frequent():
    cfg = admission.TinyLFUConfig(width=256, door_bits=512, sample=100_000)
    st = admission.make_sketch(cfg)
    hot, cold = jnp.array([1], jnp.uint32), jnp.array([2], jnp.uint32)
    for _ in range(8):
        st = admission.record(cfg, st, hot)
    st = admission.record(cfg, st, cold)
    # hot candidate vs cold victim: admit
    assert bool(admission.admit(cfg, st, hot, cold, jnp.array([True]))[0])
    # cold candidate vs hot victim: reject
    assert not bool(admission.admit(cfg, st, cold, hot, jnp.array([True]))[0])


def test_replay_kway_close_to_full(rng):
    """Paper conclusion: k=8 hit ratio within ~2pts of fully associative."""
    tr = traces.generate("zipf", 30_000, seed=3, catalog=1 << 13, alpha=1.0)
    cap = 512
    h8 = replay(SimConfig(KWayConfig(num_sets=cap // 8, ways=8, policy=Policy.LRU)), tr)
    hf = replay(SimConfig(fully_associative(cap, Policy.LRU)), tr)
    assert abs(h8 - hf) < 0.03
    assert h8 > 0.2  # sanity: the trace is cacheable


def test_replay_batched_close_to_serial(rng):
    tr = traces.generate("zipf", 20_000, seed=5, catalog=1 << 12, alpha=1.0)
    cfg = KWayConfig(num_sets=64, ways=8, policy=Policy.LRU)
    hs = replay(SimConfig(cfg), tr)
    hb = replay_batched(SimConfig(cfg), tr, batch=64)
    assert abs(hs - hb) < 0.03


def test_tinylfu_helps_on_scan(rng):
    """Admission filter shields the cache from scan pollution."""
    tr_hot = traces.generate("zipf", 15_000, seed=7, catalog=1 << 10, alpha=1.2)
    tr_scan = traces.generate("scan_loop", 15_000, seed=8, working=1 << 14,
                              noise=0.0, catalog=1 << 15)
    tr = np.empty(30_000, np.uint32)
    tr[0::2] = tr_hot
    tr[1::2] = tr_scan + np.uint32(1 << 20)
    cap = 512
    cfg = KWayConfig(num_sets=cap // 8, ways=8, policy=Policy.LFU)
    plain = replay(SimConfig(cfg), tr)
    gated = replay(SimConfig(cfg, admission.for_capacity(cap)), tr)
    assert gated >= plain - 0.01  # TinyLFU should not hurt, usually helps


def test_batched_tinylfu_matches_sequential(rng):
    """The batched replay path must honour SimConfig.tinylfu (it used to
    drop it silently): batched+TinyLFU ≈ sequential+TinyLFU hit ratio."""
    tr_hot = traces.generate("zipf", 10_000, seed=7, catalog=1 << 10,
                             alpha=1.2)
    tr_scan = traces.generate("scan_loop", 10_000, seed=8, working=1 << 14,
                              noise=0.0, catalog=1 << 15)
    tr = np.empty(20_000, np.uint32)
    tr[0::2] = tr_hot
    tr[1::2] = tr_scan + np.uint32(1 << 20)
    cap = 512
    cfg = KWayConfig(num_sets=cap // 8, ways=8, policy=Policy.LFU)
    tl = admission.for_capacity(cap)
    hs = replay(SimConfig(cfg, tl), tr)
    hb = replay_batched(SimConfig(cfg, tl), tr, batch=64)
    assert abs(hs - hb) < 0.03
    # ... and the filter visibly bites in the batched path too: without it
    # the scan pollutes the LFU cache (same direction as the serial test).
    plain = replay_batched(SimConfig(cfg), tr, batch=64)
    assert hb >= plain - 0.03


def test_batched_tinylfu_unsupported_paths_raise():
    """Only the sequential-Python ref oracle stays excluded: TinyLFU and
    two_phase now compose with the set-sharded layer (PR 4), so the old
    TinyLFU×shards / two_phase×shards guards are gone."""
    import pytest

    cfg = KWayConfig(num_sets=8, ways=8, policy=Policy.LFU)
    tl = admission.for_capacity(64)
    tr = traces.generate("zipf", 256, seed=1)
    with pytest.raises(ValueError, match="ref backend"):
        replay_batched(SimConfig(cfg, tl, backend="ref"), tr, batch=64)
    with pytest.raises(ValueError, match="ref backend"):
        replay(SimConfig(cfg, tl, backend="ref"), tr)
    with pytest.raises(ValueError, match="sharded"):
        replay_batched(SimConfig(cfg, backend="ref"), tr, batch=64, shards=2)
    # ... and the previously guarded combinations now replay fine:
    assert 0.0 <= replay_batched(SimConfig(cfg, tl), tr, batch=64,
                                 shards=2) <= 1.0
    assert 0.0 <= replay_batched(SimConfig(cfg, two_phase=True), tr,
                                 batch=64, shards=2) <= 1.0


def test_all_trace_families_generate():
    for fam in traces.FAMILIES:
        t = traces.generate(fam, 2000, seed=1)
        assert t.shape == (2000,) and t.dtype == np.uint32


def _parent_inline_replay(be, tl, access, state, chunks, enabled, sketch):
    """The replay scan's TinyLFU step as it was written inline before
    ``access_admitted``: record, peek on the batch-start state, admit on
    the recorded sketch, then ``access`` with that mask."""
    def step(carry, xs):
        cache, sk = carry
        keys, en = xs
        sk = admission.record(tl, sk, keys, enabled=en)
        vk, vv = be.peek_victims(cache, keys)
        admit = admission.admit(tl, sk, keys, vk, vv)
        cache, hit, _, _, ev = access(cache, keys, keys.astype(jnp.int32),
                                      admit, en)
        return (cache, sk), (jnp.sum(hit.astype(jnp.int32)),
                             jnp.sum(ev.astype(jnp.int32)))
    (state, sk), (hits, evs) = jax.lax.scan(step, (state, sketch),
                                            (chunks, enabled))
    return hits, evs, state, sk


@pytest.mark.parametrize("two_phase", [False, True])
def test_replay_tinylfu_branch_runs_access_admitted(two_phase):
    """The scan's TinyLFU branch (through ``CacheBackend.access_admitted``)
    gives, bit for bit, the per-chunk counts, cache and sketch of the
    inline step it replaced, over chunks that mix hits and misses, fill
    and overflow sets, carry a padded tail, and age the sketch twice; and
    ``access_admitted`` called chunk by chunk gives the same."""
    cfg = KWayConfig(num_sets=64, ways=8, policy=Policy.LRU)
    tl = admission.TinyLFUConfig(width=512, door_bits=1024, sample=1500)
    be = make_backend("jnp", cfg)
    rng = np.random.default_rng(20)
    chunks = (rng.zipf(1.2, (24, 128)) % 1500).astype(np.uint32)
    enabled = np.ones(chunks.shape, bool)
    enabled[-1, 100:] = False
    access = be.access_two_phase if two_phase else be.access
    want = jax.jit(lambda *a: _parent_inline_replay(be, tl, access, *a))(
        be.init(), jnp.asarray(chunks), jnp.asarray(enabled),
        admission.make_sketch(tl))
    got = be.replay(be.init(), chunks, enabled, tinylfu=tl,
                    two_phase=two_phase)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    hits, evs = np.asarray(want[0]), np.asarray(want[1])
    assert (hits[1:] > 0).all() and (evs > 0).sum() > 12   # mixed chunks
    assert int(want[3].additions) < 24 * 128 - 2 * 1500    # aged twice

    state, sk = be.init(), admission.make_sketch(tl)
    for i in range(chunks.shape[0]):
        k = jnp.asarray(chunks[i])
        state, sk, hit, _, _, ev = be.access_admitted(
            state, sk, k, k.astype(jnp.int32), tl, jnp.asarray(enabled[i]))
        assert int(jnp.sum(hit)) == hits[i] and int(jnp.sum(ev)) == evs[i]
    if not two_phase:
        for g, w in zip(jax.tree_util.tree_leaves((state, sk)),
                        jax.tree_util.tree_leaves(want[2:])):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
