"""Differential backend-equivalence suite (DESIGN.md §3).

Replays zipf traces through the `jnp`, `pallas` (interpret) and `ref`
backends and asserts identical hits, evictions and final state:

  * at batch size 1 all three are bit-identical across the policy ×
    layout × ways sweep (the ref oracle serializes batches, so B=1 is its
    exactness domain);
  * at any batch size `jnp` and `pallas` are bit-identical, including
    intra-batch duplicate keys and same-set collision ranks (they share one
    conflict-resolution apply; the kernel emits the same probe decisions).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import traces
from repro.core.backend import available_backends, make_backend
from repro.core.kway import KWayConfig
from repro.core.policies import Policy

ALL_POLICIES = [Policy.LRU, Policy.LFU, Policy.FIFO, Policy.RANDOM,
                Policy.HYPERBOLIC]
STATE_LEAVES = ("keys", "fprint", "vals", "meta_a", "meta_b", "clock")


def _assert_states_equal(sa, sb, msg=""):
    for leaf in STATE_LEAVES:
        a, b = np.asarray(getattr(sa, leaf)), np.asarray(getattr(sb, leaf))
        np.testing.assert_array_equal(a, b, err_msg=f"{msg}: {leaf}")


def _zipf(n, seed=11, catalog=256):
    return np.asarray(traces.generate("zipf", n, seed=seed, catalog=catalog),
                      np.uint32)


def test_registry():
    assert available_backends() == ["jnp", "pallas", "ref"]
    with pytest.raises(ValueError):
        make_backend("cuda", KWayConfig(num_sets=4, ways=2))


def test_pallas_rejects_unsupported():
    with pytest.raises(ValueError):
        make_backend("pallas", KWayConfig(num_sets=2, ways=256))
    with pytest.raises(ValueError):
        make_backend("pallas", KWayConfig(num_sets=1, ways=64, sample=8))


def test_pallas_refuses_cache_over_vmem_limit():
    """A cache whose lanes the probe kernels cannot hold in VMEM is refused
    with the numbers, on every flat path — never handed to a kernel the TPU
    compiler would reject, nor demoted to the probe-driven scan."""
    from repro.core import backend as backend_mod
    cfg = KWayConfig(num_sets=1 << 15, ways=8)
    pb = make_backend("pallas", cfg)
    assert not pb.probe_fits()
    st = pb.init()
    keys = jnp.arange(8, dtype=jnp.uint32)
    chunks, en = keys[None], jnp.ones((1, 8), bool)
    for call in (lambda: pb.get(st, keys),
                 lambda: pb.put(st, keys, keys.astype(jnp.int32)),
                 lambda: pb.access(st, keys, keys.astype(jnp.int32)),
                 lambda: pb.peek_victims(st, keys),
                 lambda: pb.replay(st, chunks, en)):
        with pytest.raises(ValueError, match="VMEM"):
            call()
    with backend_mod.vmem_budget(0):      # no resident path: same refusal
        with pytest.raises(ValueError, match="num_sets=32768"):
            pb.replay(st, chunks, en)
    # a hierarchy keeps the L2 in HBM and still replays this capacity
    from repro.core.hierarchy import HierarchyConfig
    hits, _, _, _ = pb.replay(st, chunks, en,
                              hierarchy=HierarchyConfig(l1_sets=8, l1_ways=4))
    assert int(hits.sum()) == 0


@pytest.mark.parametrize("policy", ALL_POLICIES)
@pytest.mark.parametrize("layout", ["soa", "aos"])
def test_serial_equivalence_policies(policy, layout):
    """B=1 zipf replay: identical hit/eviction sequences and final state."""
    cfg = KWayConfig(num_sets=8, ways=4, policy=policy, layout=layout)
    bes = {n: make_backend(n, cfg) for n in ("jnp", "pallas", "ref")}
    states = {n: be.init() for n, be in bes.items()}
    trace = _zipf(150, seed=int(policy), catalog=120)
    trace[::13] = 0          # key 0 must behave like any other key
    for t in trace:
        k = jnp.asarray([t], jnp.uint32)
        v = jnp.asarray([int(t)], jnp.int32)
        res = {}
        for n, be in bes.items():
            states[n], hit, vals, ek, ev = be.access(states[n], k, v)
            res[n] = (bool(hit[0]), int(vals[0]), bool(ev[0]),
                      int(ek[0]) if bool(ev[0]) else -1)
        assert res["jnp"] == res["pallas"] == res["ref"], (policy, layout, t)
    _assert_states_equal(states["jnp"], states["pallas"], f"{policy}/pallas")
    _assert_states_equal(states["jnp"], states["ref"], f"{policy}/ref")


@pytest.mark.parametrize("ways", [1, 2, 8])
def test_serial_equivalence_ways(ways):
    cfg = KWayConfig(num_sets=4, ways=ways, policy=Policy.LRU)
    bes = {n: make_backend(n, cfg) for n in ("jnp", "pallas", "ref")}
    states = {n: be.init() for n, be in bes.items()}
    for t in _zipf(120, seed=ways, catalog=60):
        k = jnp.asarray([t], jnp.uint32)
        v = jnp.asarray([int(t)], jnp.int32)
        hits = set()
        for n, be in bes.items():
            states[n], hit, _, _, _ = be.access(states[n], k, v)
            hits.add(bool(hit[0]))
        assert len(hits) == 1
    _assert_states_equal(states["jnp"], states["pallas"], f"w{ways}/pallas")
    _assert_states_equal(states["jnp"], states["ref"], f"w{ways}/ref")


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_batched_jnp_vs_pallas(policy, rng):
    """Any batch size: jnp and pallas agree bit-for-bit, with duplicates,
    same-set collision ranks, and batches that don't tile the kernel."""
    cfg = KWayConfig(num_sets=4, ways=4, policy=policy)
    bj, bp = make_backend("jnp", cfg), make_backend("pallas", cfg)
    sj, sp = bj.init(), bp.init()
    for step in range(12):
        b = [1, 7, 8, 32][step % 4]
        keys = rng.integers(0, 48, b).astype(np.uint32)
        keys[: b // 3] = keys[0]                      # forced duplicates
        vals = jnp.asarray(keys.astype(np.int32))
        kj = jnp.asarray(keys)
        sj, hj, vj, ekj, evj = bj.access(sj, kj, vals)
        sp, hp, vp, ekp, evp = bp.access(sp, kj, vals)
        np.testing.assert_array_equal(np.asarray(hj), np.asarray(hp))
        np.testing.assert_array_equal(np.asarray(vj), np.asarray(vp))
        np.testing.assert_array_equal(np.asarray(evj), np.asarray(evp))
        np.testing.assert_array_equal(
            np.asarray(ekj)[np.asarray(evj)], np.asarray(ekp)[np.asarray(evp)])
    _assert_states_equal(sj, sp, str(policy))


@pytest.mark.parametrize("backend", ["jnp", "pallas", "ref"])
def test_put_returns_landing_slots(backend):
    cfg = KWayConfig(num_sets=8, ways=2, policy=Policy.LRU)
    be = make_backend(backend, cfg)
    st = be.init()
    keys = jnp.asarray(np.arange(10, dtype=np.uint32))
    st, ek, ev, ss, sw = be.put(st, keys, jnp.full(10, 7, jnp.int32))
    ss, sw = np.asarray(ss), np.asarray(sw)
    kn = np.asarray(st.keys)
    assert (ss >= 0).any()
    for i in range(10):
        if ss[i] >= 0:
            assert kn[ss[i], sw[i]] == i        # the key sits where reported


@pytest.mark.parametrize("backend", ["jnp", "pallas", "ref"])
def test_slot_value_put(backend):
    """slot_value=True stores the landing slot id as the payload — the
    engine's page-id convention, in one call."""
    cfg = KWayConfig(num_sets=8, ways=2, policy=Policy.LRU)
    be = make_backend(backend, cfg)
    st = be.init()
    keys = jnp.asarray(np.arange(12, dtype=np.uint32))
    st, _, _, ss, sw = be.put(st, keys, jnp.zeros(12, jnp.int32),
                              slot_value=True)
    st, hit, vals = be.get(st, keys)
    ss, sw = np.asarray(ss), np.asarray(sw)
    vals = np.asarray(vals)
    for i in range(12):
        if ss[i] >= 0:
            assert bool(np.asarray(hit)[i])
            assert vals[i] == ss[i] * cfg.ways + sw[i]


def test_states_interchangeable_between_backends(rng):
    """A state produced by one backend is a valid input to another: every
    backend continues the same warm state to the same result."""
    cfg = KWayConfig(num_sets=8, ways=4, policy=Policy.LFU)
    bj, bp = make_backend("jnp", cfg), make_backend("pallas", cfg)
    warm_state = bj.init()
    ks = rng.integers(0, 100, 64).astype(np.uint32)
    warm_state, *_ = bj.access(
        warm_state, jnp.asarray(ks), jnp.asarray(ks.astype(np.int32)))
    probe = jnp.asarray(rng.integers(0, 100, 16).astype(np.uint32))
    vals = probe.astype(jnp.int32)
    sj, hj, vj, ekj, evj = bj.access(warm_state, probe, vals)
    sp, hp, vp, ekp, evp = bp.access(warm_state, probe, vals)
    np.testing.assert_array_equal(np.asarray(hj), np.asarray(hp))
    np.testing.assert_array_equal(np.asarray(vj), np.asarray(vp))
    np.testing.assert_array_equal(np.asarray(evj), np.asarray(evp))
    _assert_states_equal(sj, sp, "warm-state handoff")
    assert np.asarray(hj).any()  # the warm state actually carried over


@pytest.mark.parametrize("policy", ALL_POLICIES)
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_access_equals_two_phase(policy, backend, rng):
    """The fused single-probe ``access`` == the two-phase get-then-put
    composition, bit-for-bit at any batch size: hits, vals, evictions and
    final state — including duplicate keys, same-set collision ranks,
    enabled masks, and batches that don't tile the kernel."""
    cfg = KWayConfig(num_sets=4, ways=4, policy=policy)
    be = make_backend(backend, cfg)
    sf, st = be.init(), be.init()
    for step in range(8):
        b = [1, 7, 8, 32][step % 4]
        keys = rng.integers(0, 48, b).astype(np.uint32)
        keys[: b // 3] = keys[0]                      # forced duplicates
        en = None if step % 3 else jnp.asarray(rng.random(b) < 0.8)
        k = jnp.asarray(keys)
        v = jnp.asarray(keys.astype(np.int32))
        sf, hf, vf, ekf, evf = be.access(sf, k, v, enabled=en)
        st, ht, vt, ekt, evt = be.access_two_phase(st, k, v, enabled=en)
        np.testing.assert_array_equal(np.asarray(hf), np.asarray(ht))
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(vt))
        np.testing.assert_array_equal(np.asarray(evf), np.asarray(evt))
        np.testing.assert_array_equal(
            np.asarray(ekf)[np.asarray(evf)], np.asarray(ekt)[np.asarray(evt)])
    _assert_states_equal(sf, st, f"{backend}/{policy}: fused vs two-phase")


@pytest.mark.parametrize("policy", [Policy.LRU, Policy.LFU])
def test_fused_access_equals_two_phase_sampled(policy, rng):
    """Sampled-policy configs (jnp only) take the fused path too."""
    cfg = KWayConfig(num_sets=1, ways=64, policy=policy, sample=8)
    be = make_backend("jnp", cfg)
    sf, st = be.init(), be.init()
    for step in range(6):
        keys = rng.integers(0, 200, 16).astype(np.uint32)
        k = jnp.asarray(keys)
        v = jnp.asarray(keys.astype(np.int32))
        sf, hf, *_ = be.access(sf, k, v)
        st, ht, *_ = be.access_two_phase(st, k, v)
        np.testing.assert_array_equal(np.asarray(hf), np.asarray(ht))
    _assert_states_equal(sf, st, f"sampled/{policy}")


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("policy", [Policy.LRU, Policy.LFU])
def test_fused_access_equals_two_phase_tinylfu(backend, policy):
    """±TinyLFU: the fused path under admission gating replays to the same
    hit count and final state as the two-phase path, through the B=1 replay
    ``simulate.replay`` runs."""
    from repro.core import admission, router
    cfg = KWayConfig(num_sets=8, ways=4, policy=policy)
    tl = admission.for_capacity(32)
    chunks, en = router.pad_chunks(
        traces.generate("zipf", 250, seed=3, catalog=64), 1)
    be = make_backend(backend, cfg)
    hf, ef, sf, _ = be.replay(be.init(), chunks, en, tinylfu=tl)
    ht, et, st, _ = be.replay(be.init(), chunks, en, tinylfu=tl,
                              two_phase=True)
    np.testing.assert_array_equal(np.asarray(hf), np.asarray(ht))
    np.testing.assert_array_equal(np.asarray(ef), np.asarray(et))
    _assert_states_equal(sf, st, f"{backend}/{policy}/tinylfu")


@pytest.mark.parametrize("with_ttls", [False, True])
def test_ref_replay_matches_jnp_replay(with_ttls):
    """The ref oracle's host-loop replay equals the jnp scan at B=1: per
    chunk hits and evictions and the final state, ±per-request TTLs."""
    from repro.core import router
    cfg = KWayConfig(num_sets=8, ways=4, policy=Policy.LRU)
    tr = _zipf(200, seed=5, catalog=64)
    chunks, en = router.pad_chunks(tr, 1)
    tt = None
    if with_ttls:
        rng = np.random.default_rng(5)
        tt = rng.integers(0, 40, tr.shape[0]).astype(np.int32)[:, None]
    got = {}
    for name in ("ref", "jnp"):
        be = make_backend(name, cfg)
        got[name] = be.replay(be.init(ttl=with_ttls), chunks, en, ttls=tt)
    (hr, er, sr, _), (hj, ej, sj, _) = got["ref"], got["jnp"]
    np.testing.assert_array_equal(np.asarray(hr), np.asarray(hj))
    np.testing.assert_array_equal(np.asarray(er), np.asarray(ej))
    _assert_states_equal(sr, sj, f"ref vs jnp, ttls={with_ttls}")
    if with_ttls:
        np.testing.assert_array_equal(np.asarray(sr.expiry),
                                      np.asarray(sj.expiry))
        assert int(np.asarray(hj).sum()) > 0


def test_ref_access_is_two_phase_and_matches_fused(rng):
    """The ref oracle's ``access`` with TTLs off IS the two-phase
    composition (its override only adds expiry semantics, DESIGN.md §15),
    and the fused jnp path still matches it at B=1."""
    cfg = KWayConfig(num_sets=8, ways=4, policy=Policy.HYPERBOLIC)
    br, bj = make_backend("ref", cfg), make_backend("jnp", cfg)
    sr, s1, s2 = br.init(), bj.init(), bj.init()
    s3 = br.init()
    for t in _zipf(80, seed=9, catalog=40):
        k = jnp.asarray([t], jnp.uint32)
        v = jnp.asarray([int(t)], jnp.int32)
        sr, hr, *_ = br.access(sr, k, v)
        s1, h1, *_ = bj.access(s1, k, v)
        s2, h2, *_ = bj.access_two_phase(s2, k, v)
        s3, h3, *_ = br.access_two_phase(s3, k, v)
        assert bool(hr[0]) == bool(h1[0]) == bool(h2[0]) == bool(h3[0])
    _assert_states_equal(sr, s1, "ref vs jnp fused")
    _assert_states_equal(s1, s2, "jnp fused vs jnp two-phase")
    _assert_states_equal(sr, s3, "ref access vs ref two-phase")


def test_access_donated_matches_and_consumes_state():
    """The donating entry point returns the same result as the plain fused
    path while updating the KWayState buffers in place (the donated input
    is dead afterwards on backends that implement donation)."""
    from repro.core import kway
    cfg = KWayConfig(num_sets=8, ways=4, policy=Policy.LRU)
    keys = jnp.asarray(np.arange(16, dtype=np.uint32))
    vals = keys.astype(jnp.int32)
    s_plain, h1, v1, *_ = kway.access(cfg, kway.make_cache(cfg), keys, vals)
    s0 = kway.make_cache(cfg)
    s_don, h2, v2, *_ = kway.access_donated(cfg, s0, keys, vals)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    _assert_states_equal(s_plain, s_don, "donated")
    # the in-place chaining pattern every replay loop uses
    s_don, *_ = kway.access_donated(cfg, s_don, keys, vals)
    assert int(s_don.clock) == 64
    if hasattr(s0.keys, "is_deleted"):
        # jax with donation support consumed the input buffers
        assert s0.keys.is_deleted()


def test_peek_victims_agree(rng):
    cfg = KWayConfig(num_sets=4, ways=2, policy=Policy.LRU)
    bes = {n: make_backend(n, cfg) for n in ("jnp", "pallas", "ref")}
    st = bes["jnp"].init()
    warm = rng.integers(0, 64, 32).astype(np.uint32)
    for t in warm:  # warm sequentially so all backends see one state
        st, *_ = bes["jnp"].access(
            st, jnp.asarray([t], jnp.uint32), jnp.asarray([int(t)], jnp.int32))
    probes = jnp.asarray(rng.integers(0, 128, 16).astype(np.uint32))
    outs = {n: be.peek_victims(st, probes) for n, be in bes.items()}
    vkj, vvj = (np.asarray(x) for x in outs["jnp"])
    for n in ("pallas", "ref"):
        vk, vv = (np.asarray(x) for x in outs[n])
        np.testing.assert_array_equal(vvj, vv, err_msg=n)
        np.testing.assert_array_equal(vkj[vvj], vk[vv], err_msg=n)
