"""Trace-resident replay megakernel (kernels/replay.py) differential suite.

The megakernel replays a whole chunked trace in ONE pallas launch with the
cache state lanes (and TinyLFU sketch) pinned in VMEM; its contract is
bit-identity with the chunked-scan replay (``CacheBackend.replay`` default:
one ``lax.scan`` through the fused ``access`` with the batched TinyLFU
phases).  This file pins that contract on the golden trace across every
pallas-supported policy × ±TinyLFU — per-chunk hit counts, per-chunk
eviction counts, the final state (all five lanes + clock) and the final
sketch — plus the compile/launch economy: a whole replay is exactly one
XLA compilation and one launch.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admission, router, traces
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig
from repro.core.policies import Policy
from repro.kernels import replay as kreplay
from tests.test_golden_trace import CONFIG, golden_trace

PALLAS_POLICIES = [Policy.LRU, Policy.LFU, Policy.FIFO, Policy.RANDOM,
                   Policy.HYPERBOLIC]
BATCH = 32     # golden trace (512 requests) -> 16 chunks


def _golden_chunks():
    return router.pad_chunks(golden_trace(), BATCH)


def _assert_state_equal(a, b, label):
    for f in ("keys", "fprint", "vals", "meta_a", "meta_b", "clock"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{label}: state lane {f} diverged")


def _assert_sketch_equal(a, b, label):
    np.testing.assert_array_equal(np.asarray(a.packed), np.asarray(b.packed),
                                  err_msg=f"{label}: sketch counters")
    np.testing.assert_array_equal(np.asarray(a.door), np.asarray(b.door),
                                  err_msg=f"{label}: sketch doorkeeper")
    assert int(a.additions) == int(b.additions), f"{label}: sketch additions"


@pytest.mark.parametrize("policy", PALLAS_POLICIES)
@pytest.mark.parametrize("admission_on", [False, True],
                         ids=["none", "tinylfu"])
def test_resident_golden_parity(policy, admission_on):
    """Megakernel == chunked-scan replay on the golden trace: per-chunk
    hits and evictions, final state, final sketch — for every
    pallas-supported policy, with and without TinyLFU admission."""
    cfg = KWayConfig(policy=policy, **CONFIG)
    tl = admission.for_capacity(cfg.capacity) if admission_on else None
    chunks, en = _golden_chunks()

    jb = make_backend("jnp", cfg)        # chunked-scan oracle
    pb = make_backend("pallas", cfg)     # the megakernel under test
    assert pb.resident_fits()
    h1, e1, st1, sk1 = jb.replay(jb.init(), chunks, en, tinylfu=tl)
    h2, e2, st2, sk2 = pb.replay(pb.init(), chunks, en, tinylfu=tl)

    label = f"{policy.name}/{'tinylfu' if admission_on else 'none'}"
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2),
                                  err_msg=f"{label}: per-chunk hits")
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2),
                                  err_msg=f"{label}: per-chunk evictions")
    _assert_state_equal(st1, st2, label)
    if admission_on:
        _assert_sketch_equal(sk1, sk2, label)
    else:
        assert sk1 is None and sk2 is None


def test_resident_matches_pallas_scan_oracle():
    """The pallas backend's own chunked-scan fallback (``replay_scan``) is
    the same oracle — resident and scan agree on the kernel substrate too,
    not just across backends."""
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    chunks, en = _golden_chunks()
    pb = make_backend("pallas", cfg)
    h1, e1, st1, _ = pb.replay_scan(pb.init(), chunks, en)
    h2, e2, st2, _ = pb.replay(pb.init(), chunks, en)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    _assert_state_equal(st1, st2, "pallas scan vs resident")


def test_resident_odd_tail_padding():
    """A trace whose length is not a batch multiple: the padded tail chunk's
    disabled lanes must not perturb the replay (they still consume logical
    timestamps, like every batched path)."""
    tr = traces.generate("zipf", 501, seed=11, catalog=96)
    chunks, en = router.pad_chunks(tr, BATCH)
    assert not bool(en[-1].all())          # the tail really is padded
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    jb, pb = make_backend("jnp", cfg), make_backend("pallas", cfg)
    h1, e1, st1, _ = jb.replay(jb.init(), chunks, en)
    h2, e2, st2, _ = pb.replay(pb.init(), chunks, en)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    _assert_state_equal(st1, st2, "odd tail")


def test_resident_single_compile_single_launch():
    """The compile/launch economy proof: one whole-trace replay is exactly
    ONE pallas launch, and re-running the same shape never re-compiles."""
    cfg = KWayConfig(policy=Policy.LFU, **CONFIG)
    # a chunk width no other test uses, so the jit cache is provably cold
    chunks, en = router.pad_chunks(golden_trace(), 16)
    pb = make_backend("pallas", cfg)

    kreplay.reset_trace_counts()
    pb.replay(pb.init(), chunks, en)
    tc = kreplay.trace_counts()
    compiles = sum(v for k, v in tc.items() if k[0] == "trace")
    launches = sum(v for k, v in tc.items() if k[0] == "launch")
    assert compiles == 1, f"whole replay took {compiles} compiles (want 1)"
    assert launches == 1, f"whole replay took {launches} launches (want 1)"

    # same shape again: one more launch, ZERO fresh compilations
    pb.replay(pb.init(), chunks, en)
    tc = kreplay.trace_counts()
    assert sum(v for k, v in tc.items() if k[0] == "trace") == 1
    assert sum(v for k, v in tc.items() if k[0] == "launch") == 2


def test_resident_simulate_entry_point():
    """simulate.replay_batched on pallas (the megakernel, picked from the
    VMEM fit) == the pallas chunked scan == the jnp replay, ±TinyLFU — the
    harness-facing equality the CI gate enforces."""
    from repro.core.simulate import SimConfig, replay_batched

    tr = traces.generate("zipf", 2000, seed=3, catalog=2048)
    cfg = KWayConfig(num_sets=64, ways=8, policy=Policy.LRU)
    tl = admission.for_capacity(cfg.capacity)
    pb = make_backend("pallas", cfg)
    assert pb.resident_fits()
    chunks, en = router.pad_chunks(tr, 128)
    for tlc in (None, tl):
        a = replay_batched(SimConfig(cache=cfg, backend="pallas",
                                     tinylfu=tlc), tr, batch=128)
        h, _, _, _ = pb.replay_scan(pb.init(), chunks, en, tinylfu=tlc)
        b = float(jnp.sum(h)) / len(tr)
        c = replay_batched(SimConfig(cache=cfg, tinylfu=tlc), tr, batch=128)
        assert a == b == c, (tlc is not None, a, b, c)


def test_resident_sharded_is_d_launches():
    """Sharded resident replay: D megakernels for the whole trace (not
    D × chunks launches), bit-identical to the sharded scanned replay."""
    from repro.core.sharded import ShardedCache, ShardedConfig

    tr = traces.generate("zipf", 2000, seed=5, catalog=2048)
    cfg = KWayConfig(num_sets=64, ways=8, policy=Policy.LRU)
    d = 4
    h1, df1, st1 = ShardedCache(ShardedConfig(
        cache=cfg, num_shards=d, backend="pallas")).replay(tr, 128)

    kreplay.reset_trace_counts()
    h2, df2, st2 = ShardedCache(ShardedConfig(
        cache=cfg, num_shards=d, backend="pallas")).replay(
            tr, 128, resident=True)
    tc = kreplay.trace_counts()
    assert sum(v for k, v in tc.items() if k[0] == "launch") == d
    assert sum(v for k, v in tc.items() if k[0] == "trace") == 1

    assert (h1, df1) == (h2, df2)
    _assert_state_equal(st1, st2, "sharded resident")


def test_resident_sharded_tinylfu_parity():
    """Per-shard TinyLFU sketches ride inside each shard's megakernel and
    match the scanned shard-body phases exactly."""
    from repro.core.sharded import ShardedCache, ShardedConfig

    tr = traces.generate("zipf", 1999, seed=6, catalog=2048)  # padded tail
    cfg = KWayConfig(num_sets=64, ways=8, policy=Policy.LFU)
    tl = admission.for_capacity(cfg.capacity)
    for d in (1, 2):
        h1, _, st1 = ShardedCache(ShardedConfig(
            cache=cfg, num_shards=d, backend="pallas")).replay(
                tr, 128, tinylfu=tl)
        h2, _, st2 = ShardedCache(ShardedConfig(
            cache=cfg, num_shards=d, backend="pallas")).replay(
                tr, 128, tinylfu=tl, resident=True)
        assert h1 == h2, (d, h1, h2)
        _assert_state_equal(st1, st2, f"sharded tinylfu D={d}")


def test_resident_vmem_fallback():
    """A state too large for the VMEM budget falls back to the chunked-scan
    path — same results, no crash.  Uses the ``vmem_budget`` context
    manager (the budget knob every figure and chaos harness shares)."""
    from repro.core import backend as backend_mod

    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    chunks, en = _golden_chunks()
    pb = make_backend("pallas", cfg)
    with backend_mod.vmem_budget(1024):
        assert not pb.resident_fits()
        kreplay.reset_trace_counts()
        h1, e1, st1, _ = pb.replay(pb.init(), chunks, en)
        assert sum(kreplay.trace_counts().values()) == 0  # no megakernel ran
    assert pb.resident_fits()          # budget restored on exit
    jb = make_backend("jnp", cfg)
    h2, e2, st2, _ = jb.replay(jb.init(), chunks, en)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    _assert_state_equal(st1, st2, "vmem fallback")


def test_resident_excludes_two_phase_and_ref():
    """The replay contract: the ref oracle's host loop has no admission,
    TTLs and TinyLFU do not compose (refused by the backend itself), and a
    ``two_phase`` replay on pallas runs the chunked scan — the megakernel is
    the fused access path only — with the scan's results."""
    from repro.core.simulate import SimConfig, replay_batched

    tr = traces.generate("zipf", 256, seed=1, catalog=96)
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    tl = admission.for_capacity(cfg.capacity)
    chunks, en = router.pad_chunks(tr, 32)
    with pytest.raises(ValueError, match="ref backend"):
        rb = make_backend("ref", cfg)
        rb.replay(rb.init(), chunks, en, tinylfu=tl)
    with pytest.raises(ValueError, match="TinyLFU"):
        jb = make_backend("jnp", cfg)
        jb.replay(jb.init(ttl=True), chunks, en, tinylfu=tl,
                  ttls=np.ones(chunks.shape, np.int32))
    pb = make_backend("pallas", cfg)
    assert pb.resident_fits()
    kreplay.reset_trace_counts()
    h1, e1, st1, _ = pb.replay(pb.init(), chunks, en, two_phase=True)
    assert sum(kreplay.trace_counts().values()) == 0  # no megakernel ran
    h2, e2, st2, _ = pb.replay_scan(pb.init(), chunks, en, two_phase=True)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    _assert_state_equal(st1, st2, "pallas two_phase")
    assert replay_batched(SimConfig(cache=cfg, backend="pallas",
                                    two_phase=True), tr, batch=32) == \
        float(np.asarray(h1).sum()) / len(tr)


@pytest.mark.parametrize("shards", [1, 2])
def test_hierarchy_two_phase_refused_alike(shards):
    """Hierarchy × ``two_phase`` is refused with the one message of the
    backend's check, sharded or not."""
    from repro.core.hierarchy import HierarchyConfig
    from repro.core.simulate import SimConfig, replay_batched

    tr = traces.generate("zipf", 256, seed=1, catalog=96)
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    with pytest.raises(ValueError, match="fused sequential-lane path"):
        replay_batched(SimConfig(cache=cfg, two_phase=True), tr, batch=32,
                       shards=shards,
                       hierarchy=HierarchyConfig(l1_sets=4, l1_ways=4))


def test_resident_state_carry_midstream():
    """Replays compose: resident replay of the first half, then the scan
    replay of the second half from the returned state, equals one scanned
    replay of the whole trace (states are interchangeable mid-stream, the
    CacheBackend contract)."""
    tr = traces.generate("zipf", 1024, seed=8, catalog=96)
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    chunks, en = router.pad_chunks(tr, BATCH)
    half = len(chunks) // 2

    jb, pb = make_backend("jnp", cfg), make_backend("pallas", cfg)
    h_a, _, st_mid, _ = pb.replay(pb.init(), chunks[:half], en[:half])
    h_b, _, st_end, _ = jb.replay(st_mid, chunks[half:], en[half:])
    h_full, _, st_full, _ = jb.replay(jb.init(), chunks, en)
    assert int(jnp.sum(h_a) + jnp.sum(h_b)) == int(jnp.sum(h_full))
    _assert_state_equal(st_end, st_full, "midstream carry")


def test_resident_random_traces_sweep():
    """Randomized differential sweep beyond the golden trace: batch sizes
    that exercise intra-chunk collisions (dedupe, rank, per-lane victim
    orders) on the hash-sensitive policies."""
    for seed, batch, policy in ((21, 64, Policy.RANDOM),
                                (22, 64, Policy.HYPERBOLIC),
                                (23, 128, Policy.LRU)):
        tr = traces.generate("zipf", 1500, seed=seed, catalog=512)
        chunks, en = router.pad_chunks(tr, batch)
        cfg = KWayConfig(num_sets=32, ways=8, policy=policy)
        jb, pb = make_backend("jnp", cfg), make_backend("pallas", cfg)
        h1, e1, st1, _ = jb.replay(jb.init(), chunks, en)
        h2, e2, st2, _ = pb.replay(pb.init(), chunks, en)
        label = f"seed={seed}/{policy.name}/B={batch}"
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2),
                                      err_msg=label)
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2),
                                      err_msg=label)
        _assert_state_equal(st1, st2, label)


def test_resident_nonstandard_sketch_width():
    """TinyLFU widths that do not fill a 128-lane row (the golden config's
    width-64 sketch packs into 8 words) round-trip through the kernel's
    padded layout without corrupting the unpadded words."""
    cfg = KWayConfig(policy=Policy.LRU, **CONFIG)
    tl = admission.TinyLFUConfig(width=64, door_bits=128, sample=96)
    chunks, en = _golden_chunks()
    jb, pb = make_backend("jnp", cfg), make_backend("pallas", cfg)
    h1, _, st1, sk1 = jb.replay(jb.init(), chunks, en, tinylfu=tl)
    h2, _, st2, sk2 = pb.replay(pb.init(), chunks, en, tinylfu=tl)
    # sample=96 < trace length: the aging reset fires mid-replay
    assert int(sk1.additions) < 512
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    _assert_state_equal(st1, st2, "narrow sketch")
    _assert_sketch_equal(sk1, sk2, "narrow sketch")


def test_resident_figure_and_gate():
    """The --resident-compare surface: the figure emits the resident-eq
    records and the equality gate passes on them (and fails loudly on a
    doctored record)."""
    from benchmarks.throughput import resident_equality_gate

    records = [{"id": "resident-eq/zipf/LRU/none", "value": 0.5,
                "scan_value": 0.5}]
    checked, breaches = resident_equality_gate(records)
    assert checked == 1 and not breaches
    records[0]["scan_value"] = 0.25
    checked, breaches = resident_equality_gate(records)
    assert breaches and "diverged" in breaches[0]
    # a run with no eq records is a dead gate, not a pass
    checked, breaches = resident_equality_gate([])
    assert checked == 0 and breaches
