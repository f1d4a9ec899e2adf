"""Two-level replay hierarchy (DESIGN.md §14) differential suite.

The hierarchical megakernel (kernels/replay.replay_hierarchical: VMEM L1
over slow-memory L2) must be bit-identical with the jitted chunked-scan
twin (core/hierarchy.replay_l1_over_l2) — per-chunk hits, per-chunk
evictions, BOTH final tier states — across every pallas-supported policy
and both movement switches.  This file pins that contract on the golden
trace, plus:

  * ``l1_sets=0`` disables the hierarchy bit-exactly (flat-path parity);
  * hit-ratio bands against the flat oracles: the hierarchy beats its own
    L2 alone and tracks a flat cache of the same total capacity;
  * the phase-transition unit semantics (promotion clears the L2 slot;
    demotion lands in the victim's own set and counts an eviction only
    when it displaces an occupied entry);
  * sharded replay parity + the one-trace/one-launch-per-shard economy;
  * the loud guards (TinyLFU × hierarchy, config validation) and the
    ``l1_demotion`` degradation event under a VMEM budget breach.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as backend_mod
from repro.core import hierarchy as H
from repro.core import router, simulate, trace_io, traces
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig
from repro.core.policies import Policy
from repro.core.sharded import ShardedCache, ShardedConfig
from repro.core.simulate import SimConfig
from repro.kernels import replay as kreplay
from repro.kernels.kway_probe import LANES
from repro.robust import events
from tests.test_golden_trace import CONFIG, golden_trace
from tests.test_resident import _assert_state_equal

PALLAS_POLICIES = [Policy.LRU, Policy.LFU, Policy.FIFO, Policy.RANDOM,
                   Policy.HYPERBOLIC]
BATCH = 32
HIER = H.HierarchyConfig(l1_sets=8, l1_ways=16)


def _golden_chunks():
    return router.pad_chunks(golden_trace(), BATCH)


def _assert_hier_equal(a, b, label):
    _assert_state_equal(a.l1, b.l1, f"{label}/L1")
    _assert_state_equal(a.l2, b.l2, f"{label}/L2")


# ---------------------------------------------------------------------------
# kernel == twin, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", PALLAS_POLICIES)
def test_hier_kernel_matches_twin_golden(policy):
    cfg = KWayConfig(policy=policy, **CONFIG)
    chunks, en = _golden_chunks()
    pb = make_backend("pallas", cfg)
    jb = make_backend("jnp", cfg)
    h1, e1, st1, _ = pb.replay(pb.init(), chunks, en, hierarchy=HIER)
    h2, e2, st2, _ = jb.replay(jb.init(), chunks, en, hierarchy=HIER)
    label = f"hier/{policy.name}"
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2),
                                  err_msg=f"{label}: per-chunk hits")
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2),
                                  err_msg=f"{label}: per-chunk evictions")
    _assert_hier_equal(st1, st2, label)


@pytest.mark.parametrize("promote,demote",
                         [(True, False), (False, True), (False, False)],
                         ids=["promote-only", "demote-only", "static"])
def test_hier_kernel_matches_twin_movement_switches(promote, demote):
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    hier = H.HierarchyConfig(l1_sets=8, l1_ways=16, promote=promote,
                             demote=demote)
    chunks, en = _golden_chunks()
    pb = make_backend("pallas", cfg)
    jb = make_backend("jnp", cfg)
    h1, e1, st1, _ = pb.replay(pb.init(), chunks, en, hierarchy=hier)
    h2, e2, st2, _ = jb.replay(jb.init(), chunks, en, hierarchy=hier)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    _assert_hier_equal(st1, st2, f"promote={promote},demote={demote}")


def test_hier_state_resumes_midstream():
    """Hierarchy replays compose: half + half from the returned HierState
    equals one whole replay (states are interchangeable mid-stream)."""
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    chunks, en = _golden_chunks()
    pb = make_backend("pallas", cfg)
    half = chunks.shape[0] // 2
    _, _, mid, _ = pb.replay(pb.init(), chunks[:half], en[:half],
                             hierarchy=HIER)
    hb, _, stb, _ = pb.replay(mid, chunks[half:], en[half:], hierarchy=HIER)
    ha, _, sta, _ = pb.replay(pb.init(), chunks, en, hierarchy=HIER)
    assert int(np.sum(np.asarray(ha)[half:])) == int(np.sum(np.asarray(hb)))
    _assert_hier_equal(sta, stb, "midstream resume")


# ---------------------------------------------------------------------------
# l1_sets = 0: the hierarchy disabled is the flat path, exactly
# ---------------------------------------------------------------------------

def test_hier_disabled_is_flat_path_bit_exact():
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    chunks, en = _golden_chunks()
    pb = make_backend("pallas", cfg)
    off = H.HierarchyConfig(l1_sets=0)
    assert not off.enabled
    h0, e0, st0, _ = pb.replay(pb.init(), chunks, en, hierarchy=off)
    h1, e1, st1, _ = pb.replay(pb.init(), chunks, en)
    assert not isinstance(st0, H.HierState)
    np.testing.assert_array_equal(np.asarray(h0), np.asarray(h1))
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    _assert_state_equal(st0, st1, "l1_sets=0 flat parity")
    # ... and against the chunked-scan oracle too
    h2, e2, st2, _ = pb.replay_scan(pb.init(), chunks, en)
    np.testing.assert_array_equal(np.asarray(h0), np.asarray(h2))
    _assert_state_equal(st0, st2, "l1_sets=0 scan parity")


# ---------------------------------------------------------------------------
# hit-ratio bands vs the flat oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["zipf", "lirs_two_pools"])
def test_hier_hit_ratio_bands(family):
    """The hierarchy must (a) beat its own L2 running alone — the L1 adds
    capacity and a high-associativity front — and (b) stay within a tight
    band of a flat cache of the same TOTAL capacity (64×12 = 768 =
    512 + 256): tiering costs at most a few points of hit ratio, which is
    the premise of serving past the VMEM budget at resident speed."""
    trace_io.register_fixture_traces()
    kwargs = {"catalog": 4096} if family == "zipf" else {}
    tr = traces.generate(family, 4096, seed=7, **kwargs)
    l2 = KWayConfig(num_sets=64, ways=8, policy=Policy.LRU)
    hier = H.HierarchyConfig(l1_sets=16, l1_ways=16)
    hr_hier = simulate.replay_batched(
        SimConfig(cache=l2, backend="pallas"), tr, batch=64, hierarchy=hier)
    hr_l2 = simulate.replay_batched(
        SimConfig(cache=l2, backend="pallas"), tr, batch=64)
    flat = KWayConfig(num_sets=64, ways=12, policy=Policy.LRU)
    hr_flat = simulate.replay_batched(
        SimConfig(cache=flat, backend="pallas"), tr, batch=64)
    assert hr_hier >= hr_l2 + 0.02, (family, hr_hier, hr_l2)
    assert abs(hr_hier - hr_flat) <= 0.05, (family, hr_hier, hr_flat)


# ---------------------------------------------------------------------------
# phase-transition unit semantics
# ---------------------------------------------------------------------------

def _row(keys, vals=None, ma=None, mb=None, ways=4):
    """Build one packed row from short python lists (rest empty)."""
    from repro.kernels.kway_probe import _fingerprint_i32

    k = np.full(LANES, -1, np.int32)
    f = np.zeros(LANES, np.int32)
    v = np.zeros(LANES, np.int32)
    a = np.zeros(LANES, np.int32)
    b = np.zeros(LANES, np.int32)
    for i, key in enumerate(keys):
        k[i] = key
        f[i] = int(_fingerprint_i32(jnp.uint32(key)))
        v[i] = (vals or keys)[i]
        a[i] = (ma or [0] * len(keys))[i]
        b[i] = (mb or [0] * len(keys))[i]
    sc = np.zeros(LANES, np.int32)
    return jnp.asarray(np.concatenate([k, f, v, a, b, sc])[None, :])


def _fp(key):
    from repro.kernels.kway_probe import _fingerprint_i32
    return _fingerprint_i32(jnp.uint32(key))


def test_promotion_clears_l2_slot_and_carries_metadata():
    """An L2 hit with ``promote`` MOVES the entry: the L2 slot is cleared
    (exclusive tiers) and the hit-updated metadata rides the mailbox for
    the L1 fill."""
    row = _row([7, 9], vals=[70, 90], ma=[3, 5])
    out = H._l2_hit_row(int(Policy.LFU), True, row, jnp.int32(9), _fp(9),
                        jnp.bool_(False), jnp.int32(100), jnp.bool_(True),
                        4)
    out = np.asarray(out)[0]
    assert out[1] == -1                       # way 1 cleared -> EMPTY
    assert out[0] == 7                        # neighbour untouched
    sc = out[5 * LANES:]
    assert sc[H.SC_L2HIT] == 1
    assert sc[H.SC_PVAL] == 90                # promoted payload
    assert sc[H.SC_PA] == 6                   # LFU on_hit: count 5 -> 6
    # without promote: updated in place, slot intact
    out2 = np.asarray(H._l2_hit_row(
        int(Policy.LFU), False, row, jnp.int32(9), _fp(9),
        jnp.bool_(False), jnp.int32(100), jnp.bool_(True), 4))[0]
    assert out2[1] == 9
    assert out2[3 * LANES + 1] == 6           # meta_a bumped in place


def test_l1_fill_reports_displaced_victim():
    """Filling a full L1 set surfaces the displaced entry — key, payload
    and metadata — in the mailbox for the demotion phase."""
    row = _row([1, 2, 3, 4], vals=[10, 20, 30, 40], ma=[50, 20, 60, 70])
    out = H._l1_fill_row(int(Policy.LRU), True, row, jnp.int32(99), _fp(99),
                         jnp.bool_(False), jnp.bool_(False), jnp.int32(0),
                         jnp.int32(0), jnp.int32(0), jnp.int32(200),
                         jnp.bool_(True), 4)
    out = np.asarray(out)[0]
    sc = out[5 * LANES:]
    assert sc[H.SC_DVALID] == 1
    assert sc[H.SC_DK] == 2                   # LRU victim: oldest meta_a
    assert sc[H.SC_DV] == 20
    assert sc[H.SC_DA] == 20                  # metadata carried verbatim
    assert out[1] == 99                       # inserted over the victim way


def test_demotion_counts_eviction_only_on_occupied_victim():
    empty_set = _row([])
    full_set = _row([11, 12, 13, 14], ma=[1, 2, 3, 4])
    args = (jnp.int32(5), _fp(5), jnp.int32(55), jnp.int32(9), jnp.int32(0))
    out1 = H._l2_demote_row(int(Policy.LRU), empty_set, *args,
                            jnp.bool_(True), jnp.int32(300), 4)
    out2 = H._l2_demote_row(int(Policy.LRU), full_set, *args,
                            jnp.bool_(True), jnp.int32(300), 4)
    sc1 = np.asarray(out1)[0, 5 * LANES:]
    sc2 = np.asarray(out2)[0, 5 * LANES:]
    assert sc1[H.SC_EV] == 0                  # landed on an empty way
    assert sc2[H.SC_EV] == 1                  # displaced an occupied entry
    assert np.asarray(out1)[0, 0] == 5        # demoted key inserted
    assert np.asarray(out1)[0, 2 * LANES] == 55   # payload + meta carried
    assert np.asarray(out1)[0, 3 * LANES] == 9
    # an invalid victim (dvalid=False) must leave the row untouched
    out3 = H._l2_demote_row(int(Policy.LRU), full_set, *args,
                            jnp.bool_(False), jnp.int32(300), 4)
    np.testing.assert_array_equal(np.asarray(out3)[0, :5 * LANES],
                                  np.asarray(full_set)[0, :5 * LANES])


# ---------------------------------------------------------------------------
# sharded replay: parity + launch economy
# ---------------------------------------------------------------------------

def test_sharded_hier_parity_and_launch_economy():
    tr = traces.generate("zipf", 2048, seed=3, catalog=1024)
    cfg = KWayConfig(num_sets=64, ways=8, policy=Policy.LRU)
    for d in (1, 2):
        sc_p = ShardedCache(ShardedConfig(cache=cfg, num_shards=d,
                                          backend="pallas"))
        sc_j = ShardedCache(ShardedConfig(cache=cfg, num_shards=d,
                                          backend="jnp"))
        kreplay.reset_trace_counts()
        h1, d1, st1 = sc_p.replay(tr, 128, resident=True, hierarchy=HIER)
        launches = sum(v for k, v in kreplay.trace_counts().items()
                       if k[0] == "launch-hier")
        assert launches == d, f"expected one megakernel launch per shard"
        h2, d2, st2 = sc_j.replay(tr, 128, resident=True, hierarchy=HIER)
        assert (h1, d1) == (h2, d2), (d, h1, h2)
        for tier in ("l1", "l2"):
            for f in ("keys", "vals", "meta_a"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(getattr(st1, tier), f)),
                    np.asarray(getattr(getattr(st2, tier), f)),
                    err_msg=f"sharded D={d} {tier}.{f}")


def test_hier_trace_economy():
    """Same-shape hierarchical replays: ONE trace, one launch each."""
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    # a chunk width no other test uses, so the jit cache is provably cold
    chunks, en = router.pad_chunks(golden_trace(), 16)
    pb = make_backend("pallas", cfg)
    kreplay.reset_trace_counts()
    pb.replay(pb.init(), chunks, en, hierarchy=HIER)
    pb.replay(pb.init(), chunks, en, hierarchy=HIER)
    counts = kreplay.trace_counts()
    assert sum(v for k, v in counts.items() if k[0] == "trace-hier") == 1
    assert sum(v for k, v in counts.items() if k[0] == "launch-hier") == 2


# ---------------------------------------------------------------------------
# guards, budget accounting, degradation
# ---------------------------------------------------------------------------

def test_hier_config_validation():
    with pytest.raises(AssertionError):
        H.HierarchyConfig(l1_sets=6)          # not a power of two
    with pytest.raises(AssertionError):
        H.HierarchyConfig(l1_sets=8, l1_ways=LANES + 1)
    assert H.HierarchyConfig(l1_sets=0).enabled is False
    assert H.HierarchyConfig(l1_sets=8, l1_ways=16).l1_capacity == 128


def test_hier_rejects_tinylfu():
    from repro.core import admission

    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    chunks, en = _golden_chunks()
    tl = admission.for_capacity(cfg.capacity)
    for name in ("pallas", "jnp"):
        be = make_backend(name, cfg)
        with pytest.raises(ValueError, match="TinyLFU"):
            be.replay(be.init(), chunks, en, tinylfu=tl, hierarchy=HIER)


def test_hier_vmem_breach_demotes_to_twin_with_event():
    """Over budget the hierarchical kernel is abandoned for the jnp twin —
    same results bit-for-bit, with an ``l1_demotion`` degradation event
    naming the hierarchy option."""
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    chunks, en = _golden_chunks()
    pb = make_backend("pallas", cfg)
    h_ref, e_ref, st_ref, _ = pb.replay(pb.init(), chunks, en,
                                        hierarchy=HIER)
    c0 = events.cursor()
    with backend_mod.vmem_budget(0):
        assert not pb.hier_fits(HIER)
        h, e, st, _ = pb.replay(pb.init(), chunks, en, hierarchy=HIER)
    evs = [ev for ev in events.since(c0) if ev.reason == "l1_demotion"]
    assert len(evs) == 1
    assert evs[0].fallback_to == "jnp-l1l2-scan"
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(e), np.asarray(e_ref))
    _assert_hier_equal(st, st_ref, "vmem breach twin fallback")


def test_hier_footprint_accounting():
    assert H.hier_footprint_bytes(HIER) == 2 * 8 * H.ROW_W * 4
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    pb = make_backend("pallas", cfg)
    assert pb.hier_fits(HIER)
    # the budget context scales the answer, not just zeroes it
    with backend_mod.vmem_budget(H.hier_footprint_bytes(HIER)):
        assert pb.hier_fits(HIER)
    with backend_mod.vmem_budget(H.hier_footprint_bytes(HIER) - 1):
        assert not pb.hier_fits(HIER)


# ---------------------------------------------------------------------------
# fixture trace registration (satellite: real-trace-style family)
# ---------------------------------------------------------------------------

def test_fixture_trace_registered_and_deterministic():
    names = trace_io.register_fixture_traces()
    assert "lirs_two_pools" in names
    tr = traces.generate("lirs_two_pools", 10_000)
    assert len(tr) == 10_000
    assert trace_io.trace_fingerprint(tr) == "e76f5e99"
    # tiling: n beyond the file length wraps deterministically
    tr2 = traces.generate("lirs_two_pools", 12_000)
    np.testing.assert_array_equal(tr2[:10_000], tr)
    np.testing.assert_array_equal(tr2[10_000:], tr[:2_000])


def test_hierarchy_figure_quick_gate():
    """``figures.hierarchy(quick=True)``, the run behind the CI hierarchy
    gate, runs end to end, and its hit-ratio rows pass that gate against
    the checked-in baseline: ``l1-0`` equal to the flat replay, the
    enabled rows in the 0.02 band.  The gate's over-budget speedup
    headline is a claim about chip timings, which the CPU interpreter
    cannot show, so only the rows' presence is checked here."""
    from benchmarks.throughput import hierarchy_gate
    from repro.eval import figures

    _, records, _ = figures.hierarchy(quick=True)
    ids = {r["id"] for r in records}
    assert {"hier-tp/speedup/s512/batch256",
            "hier-tp/speedup/s4096/batch256"} <= ids
    checked, breaches = hierarchy_gate(
        "benchmarks/baselines/BENCH_throughput_hierarchy_quick.json",
        records)
    assert checked > len([i for i in ids if i.startswith("hier-hr/")])
    assert [b for b in breaches if "speedup" not in b] == []
