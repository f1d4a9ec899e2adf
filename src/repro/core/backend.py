"""Unified CacheBackend layer — one API over the jnp / Pallas / oracle paths.

The paper's limited-associativity design makes each set an independent unit
of work, which is why the same cache runs on three execution substrates in
this repo: vectorized XLA ops (core/kway.py), a Pallas TPU kernel
(kernels/kway_probe.py), and a sequential Python oracle (core/refimpl.py).
This module gives them one contract (DESIGN.md §3):

    backend = make_backend("jnp" | "pallas" | "ref", cfg)
    state = backend.init()
    state, hit, vals = backend.get(state, keys)
    state, ek, ev, slot_sets, slot_ways = backend.put(state, keys, vals)
    state, hit, vals, ek, ev = backend.access(state, keys, vals)
    vkeys, vvalid = backend.peek_victims(state, keys)
    state, sketch, hit, vals, ek, ev = backend.access_admitted(
        state, sketch, keys, vals, tinylfu)
    hits, evs, state, sketch = backend.replay(
        state, chunks, enabled, tinylfu=None, sketch=None, hierarchy=None,
        ttls=None, two_phase=False)

All backends are functional (state in, state out) over the same ``KWayState``
pytree, so states are interchangeable between backends mid-stream — the
differential test suite replays one trace through all three and asserts
bit-identical hits, evictions and final state.

``put`` returns the landing ``(set, way)`` slot per request (-1 when the key
did not land), which is what lets serve/engine.py store "payload == slot id"
in a single call instead of probing again after the write.

``replay`` is the one replay entry point on one device, and each path is
picked where it can be observed:
  * ``CacheBackend.replay`` is the chunked replay loop: one jitted
    scan (the program ``jit_fn``) whose options — TinyLFU
    admission, a TTL stream in the scan's ``xs``, the ``two_phase`` oracle
    — are fixed at trace time; the jnp backend runs it as is.
  * ``PallasBackend.replay`` picks the hierarchical or the flat
    trace-resident megakernel from the VMEM fit, and that same scan
    (``replay_scan``) for ``two_phase`` or a state over the budget.
  * ``RefBackend.replay`` is the oracle's host loop over the chunks.
  * ``_check_replay`` refuses, for all of them, the options that do not
    compose (TTL × TinyLFU, TTL × two_phase, hierarchy × TinyLFU,
    hierarchy × two_phase).

Semantics:
  * ``jnp`` and ``pallas`` share the deterministic batched conflict
    resolution of core/kway.apply_put and agree bit-for-bit at any batch
    size (the kernel emits the same probe decisions the jnp path computes).
  * ``ref`` processes lanes of a batch sequentially within each phase; it is
    bit-identical to the others at batch size 1 and a valid serialization at
    larger batches (the documented CAS-race outcomes may differ).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import admission, hashing, kway
from repro.core.hashing import EMPTY_KEY
from repro.core.kway import KWayConfig, KWayState
from repro.core.refimpl import RefKWay

#: VMEM budget for the trace-resident replay megakernel (DESIGN.md §10):
#: the resident footprint — input + working copies of the 5 state lanes at
#: the 128-lane padded width, plus streams and sketch — must fit the ~16 MiB
#: of a TPU core with headroom for the compiler.  Past this the flat
#: resident path is unavailable; the hierarchical kernel (DESIGN.md §14)
#: or the chunked-scan replay take over.
RESIDENT_VMEM_BUDGET = 12 << 20


@contextlib.contextmanager
def vmem_budget(nbytes: int):
    """Temporarily override ``RESIDENT_VMEM_BUDGET`` (try/finally restore).

    The chaos figures and tests force VMEM breaches by shrinking the
    budget; doing that with an inline set/restore leaks the override when
    the timed call raises mid-measurement.  This is the one sanctioned way
    to patch the budget.
    """
    global RESIDENT_VMEM_BUDGET
    prev = RESIDENT_VMEM_BUDGET
    RESIDENT_VMEM_BUDGET = nbytes
    try:
        yield
    finally:
        RESIDENT_VMEM_BUDGET = prev

#: The profiler span around a host call of a backend's ``access``: the
#: program's own host time per batch (dispatch, argument handling) on the
#: profiler's clock, beside the device ops of the call.
ACCESS_SPAN = "cache.access"


def _access_span(access):
    """Open the ``ACCESS_SPAN`` profiler span around a host call of
    ``access``.  A call traced into another program (``jit``/``scan``
    bodies, where the keys are tracers) opens none: there it runs once, at
    trace time.  Without an active profiler the span costs well under a
    microsecond."""
    @functools.wraps(access)
    def call(self, state, qkeys, *args, **kwargs):
        if isinstance(qkeys, jax.core.Tracer):
            return access(self, state, qkeys, *args, **kwargs)
        with jax.profiler.TraceAnnotation(ACCESS_SPAN):
            return access(self, state, qkeys, *args, **kwargs)
    return call


def _check_replay(tinylfu, hierarchy, ttls, two_phase):
    """Refuse the replay options no path composes; every backend's
    ``replay`` calls this one check.  Returns ``hierarchy``, or None when
    it is absent or off (``l1_sets == 0``: the flat replay)."""
    if hierarchy is not None and not hierarchy.enabled:
        hierarchy = None
    if ttls is not None and tinylfu is not None:
        raise ValueError(
            "per-request TTLs and TinyLFU admission are mutually "
            "exclusive (the sketch has no expiry-aware semantics)")
    if ttls is not None and two_phase:
        raise ValueError(
            "per-request TTLs require the fused access path; "
            "two_phase has no expiry semantics")
    if hierarchy is not None and tinylfu is not None:
        raise ValueError(
            "hierarchical replay does not support TinyLFU admission "
            "(the sketch has no per-tier semantics yet)")
    if hierarchy is not None and two_phase:
        raise ValueError(
            "hierarchical replay is the fused sequential-lane path; "
            "two_phase does not compose with it")
    return hierarchy


_REGISTRY: dict[str, type] = {}


def register_backend(name: str):
    """Class decorator: register a CacheBackend implementation under ``name``."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def make_backend(name: str, cfg: KWayConfig) -> "CacheBackend":
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown cache backend {name!r}; available: {available_backends()}"
        )
    return _REGISTRY[name](cfg)


class CacheBackend:
    """The backend contract.  Subclasses implement get/put/peek_victims;
    ``access`` (get; on miss, put) is derived and shared."""

    name = "?"
    traceable = True   # safe under jit/vmap/shard_map (False: host Python)

    def __init__(self, cfg: KWayConfig):
        self.cfg = cfg
        # (tinylfu, has_ttl, two_phase) -> the jitted chunked-scan replay,
        # whose program is named ``jit_fn``
        self._replay_fns: dict = {}

    def init(self, *, ttl: bool = False) -> KWayState:
        return kway.make_cache(self.cfg, ttl=ttl)

    # -- required ----------------------------------------------------------
    def get(self, state, qkeys, enabled=None):
        """-> (state', hit bool[B], vals int32[B])"""
        raise NotImplementedError

    def put(self, state, qkeys, qvals, admit=None, enabled=None, *,
            slot_value: bool = False):
        """-> (state', evicted_keys[B], evicted_valid[B], slot_sets[B],
        slot_ways[B]); slot_* == -1 where the key did not land."""
        raise NotImplementedError

    def peek_victims(self, state, qkeys):
        """-> (victim_keys uint32[B], victim_valid bool[B]), no mutation."""
        raise NotImplementedError

    # -- derived -----------------------------------------------------------
    def access_two_phase(self, state, qkeys, qvals, admit_on_miss=None,
                         enabled=None, *, slot_value: bool = False):
        """The unfused get-then-put-on-miss composition — two probes, two
        apply passes.  Kept on every backend as the differential oracle for
        the fused ``access`` (tests assert bit-identity).

        ``slot_value`` is the cache-as-allocator mode: the put phase stores
        slot ids as payload and ``vals`` returns, per lane, the page/slot id
        the key resides in (hit or fresh insert) or -1 where it did not
        land — the serving engine's one-call prefix-chain transaction."""
        state, hit, vals = self.get(state, qkeys, enabled=enabled)
        en = (~hit) if enabled is None else (enabled & ~hit)
        state, ek, ev, ss, sw = self.put(
            state, qkeys, qvals, admit=admit_on_miss, enabled=en,
            slot_value=slot_value,
        )
        if slot_value:
            slot_id = ss * jnp.int32(self.cfg.ways) + sw
            vals = jnp.where(hit, vals, jnp.where(ss >= 0, slot_id, -1))
        else:
            vals = jnp.where(hit, vals, qvals)
        return state, hit, vals, ek, ev

    @_access_span
    def access(self, state, qkeys, qvals, admit_on_miss=None, enabled=None,
               ttls=None, *, slot_value: bool = False):
        """-> (state', hit[B], vals[B], evicted_keys[B], evicted_valid[B])

        Backends with a fused single-probe path override this; the default
        is the two-phase composition (the ref oracle replays sequentially
        either way).  ``ttls`` (int32 [B], optional) gives each request a
        time-to-live on the logical clock (DESIGN.md §15); the two-phase
        composition has no expiry semantics, so the default rejects it.
        """
        if ttls is not None:
            raise ValueError(
                f"backend {self.name!r} access has no fused TTL path; "
                "per-request TTLs require the jnp, pallas or ref backend")
        return self.access_two_phase(state, qkeys, qvals,
                                     admit_on_miss=admit_on_miss,
                                     enabled=enabled, slot_value=slot_value)

    def access_admitted(self, state, sketch, qkeys, qvals, tinylfu,
                        enabled=None):
        """``access`` behind TinyLFU admission (``core/admission.py``): the
        batch's one admitted step, the phase order every admitted replay
        runs.

        1. ``admission.record`` counts every enabled lane into ``sketch``
           (doorkeeper, count-min counters, aging), under the
           ``tinylfu.record`` scope;
        2. ``peek_victims`` finds, on the batch-start ``state``, the key each
           request would evict (way 0 of its set's victim order);
        3. ``admission.admit`` compares the two estimates on the recorded
           sketch — 2 and 3 under the ``tinylfu.admit`` scope;
        4. ``access`` with that admit mask: a missed key that is not
           admitted changes nothing.

        ``tinylfu`` is the sketch's ``TinyLFUConfig``.  -> (state', sketch',
        hit[B], vals[B], evicted_keys[B], evicted_valid[B]).
        """
        return self._admitted(self.access, state, sketch, qkeys, qvals,
                              tinylfu, enabled)

    def _admitted(self, access, state, sketch, qkeys, qvals, tinylfu,
                  enabled):
        """``access_admitted`` through ``access`` (the fused path or the
        two-phase oracle)."""
        sketch = admission.record(tinylfu, sketch, qkeys, enabled=enabled)
        with jax.named_scope("tinylfu.admit"):
            vkeys, vvalid = self.peek_victims(state, qkeys)
            admit = admission.admit(tinylfu, sketch, qkeys, vkeys, vvalid)
        state, hit, vals, ek, ev = access(state, qkeys, qvals, admit, enabled)
        return state, sketch, hit, vals, ek, ev

    def _replay_hier(self, state, chunks, enabled, hierarchy, ttls=None):
        """Hierarchical replay through the pure-XLA twin
        (core/hierarchy.replay_l1_over_l2).  ``state`` may be a
        ``HierState`` (resumed hierarchy) or a plain ``KWayState`` (the L2;
        a fresh empty L1 is attached).  Returns (hits, evs, HierState',
        None)."""
        from repro.core import hierarchy as hier_mod
        hst = hier_mod.as_hier_state(self.cfg, hierarchy, state)
        return hier_mod.replay_l1_over_l2(self.cfg, hierarchy, hst,
                                          chunks, enabled, ttls=ttls)

    def replay(self, state, chunks, enabled, tinylfu=None, sketch=None,
               hierarchy=None, ttls=None, two_phase=False):
        """Replay a whole chunked trace: ``chunks`` uint32 [steps, B] and
        ``enabled`` bool [steps, B] in the ``router.pad_chunks`` layout,
        payload convention ``val == key`` (as int32).

        -> (hits int32 [steps], evs int32 [steps], state', sketch'|None):
        per-chunk hit and eviction counts, the final cache state, and the
        updated TinyLFU sketch when ``tinylfu`` is given.

        ``hierarchy`` (a :class:`repro.core.hierarchy.HierarchyConfig`
        with ``l1_sets > 0``) selects the L1-over-L2 replay mode: ``state``
        may then be a ``HierState`` or a bare L2 ``KWayState``, and the
        returned state is a ``HierState``.  ``l1_sets == 0`` (or None)
        is the flat replay.

        ``ttls`` (int32 [steps, B], chunked like the trace) enables expiry
        semantics: each request's insert carries a deadline, expired
        entries are scrubbed at every batch entry and never count as hits
        (DESIGN.md §15).

        ``two_phase`` runs each chunk through ``access_two_phase``, the
        unfused get-then-put oracle, in place of the fused ``access``.

        Default implementation, and the one chunked replay loop on one
        device: a jitted scan over the chunks through ``access`` (through
        ``access_admitted`` with ``tinylfu``), the TTL stream riding in the
        scan's ``xs``.  It is the oracle the trace-resident megakernel
        (PallasBackend) is pinned against.
        """
        hierarchy = _check_replay(tinylfu, hierarchy, ttls, two_phase)
        if hierarchy is not None:
            return self._replay_hier(state, chunks, enabled, hierarchy,
                                     ttls=ttls)
        if ttls is not None:
            state = kway.ensure_expiry(state)
            ttls = jnp.asarray(ttls, jnp.int32)
        if tinylfu is not None and sketch is None:
            sketch = admission.make_sketch(tinylfu)
        if tinylfu is None and sketch is None:
            sketch = jnp.zeros((), jnp.int32)   # scan carry placeholder
        key = (tinylfu, ttls is not None, two_phase)
        if key not in self._replay_fns:
            access = self.access_two_phase if two_phase else self.access

            def fn(state, chunks, enabled, sketch, ttls, _tl=tinylfu):
                def step(carry, xs):
                    cache, sk = carry
                    keys, en, tt = xs
                    vals = keys.astype(jnp.int32)
                    if _tl is not None:
                        cache, sk, hit, _, _, ev = self._admitted(
                            access, cache, sk, keys, vals, _tl, en)
                    else:
                        ttl_kw = {} if tt is None else {"ttls": tt}
                        cache, hit, _, _, ev = access(cache, keys, vals,
                                                      None, en, **ttl_kw)
                    return (cache, sk), (jnp.sum(hit.astype(jnp.int32)),
                                         jnp.sum(ev.astype(jnp.int32)))

                (state, sk), (hits, evs) = jax.lax.scan(
                    step, (state, sketch), (chunks, enabled, ttls))
                return hits, evs, state, sk
            self._replay_fns[key] = jax.jit(fn)
        hits, evs, state, sk = self._replay_fns[key](
            jax.tree_util.tree_map(jnp.asarray, state),
            jnp.asarray(chunks, jnp.uint32), jnp.asarray(enabled, jnp.bool_),
            sketch, ttls)
        return hits, evs, state, (sk if tinylfu is not None else None)

    #: The chunked scan under its own name on every backend: ``replay``
    #: itself here, and still this scan on pallas, whose ``replay`` picks
    #: the megakernel — the megakernel's differential oracle and the path
    #: of ``two_phase`` and of a state over the VMEM budget.
    replay_scan = replay


@register_backend("jnp")
class JnpBackend(CacheBackend):
    """Today's vectorized XLA path (core/kway.py), unchanged semantics."""

    def get(self, state, qkeys, enabled=None):
        return kway.get(self.cfg, state, qkeys, enabled=enabled)

    def put(self, state, qkeys, qvals, admit=None, enabled=None, *,
            slot_value: bool = False):
        return kway.put(self.cfg, state, qkeys, qvals, admit=admit,
                        enabled=enabled, slot_value=slot_value)

    @_access_span
    def access(self, state, qkeys, qvals, admit_on_miss=None, enabled=None,
               ttls=None, *, slot_value: bool = False):
        # fused single-probe path (kway.apply_access); bit-identical to
        # access_two_phase
        return kway.access(self.cfg, state, qkeys, qvals,
                           admit_on_miss=admit_on_miss, enabled=enabled,
                           ttls=ttls, slot_value=slot_value)

    def access_donated(self, state, qkeys, qvals, admit_on_miss=None,
                       enabled=None, *, slot_value: bool = False):
        """Fused access with the ``state`` buffers donated to XLA —
        in-place update of the 5 S×k lanes.  The caller must rebind and
        never reuse the input state."""
        return kway.access_donated(self.cfg, state, qkeys, qvals,
                                   admit_on_miss, enabled,
                                   slot_value=slot_value)

    def peek_victims(self, state, qkeys):
        return kway.peek_victims(self.cfg, state, qkeys)


@register_backend("pallas")
class PallasBackend(CacheBackend):
    """Pallas kernel probe (interpret mode off-TPU) + the shared scatter
    apply.  Bit-identical to ``jnp`` at any batch size: the kernel emits the
    same (hit, way, victim-order) decisions core/kway computes, and both
    paths funnel through kway.apply_get / kway.apply_put."""

    def __init__(self, cfg: KWayConfig):
        from repro.kernels import kway_probe as _kp
        if cfg.sample:
            raise ValueError("pallas backend does not support sampled "
                             "policies (cfg.sample > 0); use the jnp backend")
        if cfg.ways > _kp.LANES:
            raise ValueError(
                f"pallas backend requires ways <= {_kp.LANES} (one VREG row "
                f"per set); got {cfg.ways}")
        super().__init__(cfg)

    def probe_fits(self) -> bool:
        """True when the probe kernels' whole-lane VMEM footprint
        (``kway_probe.probe_vmem_bytes``) fits the scoped-VMEM limit they
        compile under — the limit the TPU compiler enforces."""
        from repro.kernels import kway_probe as _kp
        return (_kp.probe_vmem_bytes(self.cfg.num_sets)
                <= _kp.VMEM_LIMIT)

    def _require_probe_fits(self):
        """Refuse, with the numbers, a cache the probe kernels cannot hold:
        they map every state lane whole into VMEM, so past the limit the
        kernel would not compile on a TPU."""
        if not self.probe_fits():
            from repro.kernels import kway_probe as _kp
            raise ValueError(
                f"pallas probe kernels hold the whole cache in VMEM: "
                f"num_sets={self.cfg.num_sets} needs "
                f"{_kp.probe_vmem_bytes(self.cfg.num_sets)} B, over the "
                f"{_kp.VMEM_LIMIT} B limit; use the jnp backend, or "
                f"replay with a hierarchy (HierarchyConfig(l1_sets>0)) that "
                f"keeps the L2 in HBM")

    def get(self, state, qkeys, enabled=None):
        from repro.kernels import ops
        self._require_probe_fits()
        # need_victims=False kernel variant: the read path skips the
        # victim-selection rounds entirely
        _, sets, hit, way = ops.probe_hits(
            self.cfg, state, jnp.asarray(qkeys, jnp.uint32))
        if enabled is not None:
            hit = hit & enabled
        return kway.apply_get(self.cfg, state, sets, hit, way)

    @_access_span
    def access(self, state, qkeys, qvals, admit_on_miss=None, enabled=None,
               ttls=None, *, slot_value: bool = False):
        # ONE kernel launch (fused probe + victim order on hit-updated
        # metadata) + the shared fused apply — bit-identical to the
        # two-launch access_two_phase path.  The expiry scrub runs before
        # the probe launch (exactly where the jnp path scrubs), so the
        # kernel itself needs no expiry awareness.
        from repro.kernels import ops
        self._require_probe_fits()
        if state.expiry is not None:
            b = jnp.asarray(qkeys).shape[0]
            state = kway.scrub_expired(state,
                                       state.clock + jnp.int32(2 * b))
        qk, sets, hit_raw, way, order = ops.fused_probe(
            self.cfg, state, jnp.asarray(qkeys, jnp.uint32), enabled)
        return kway.apply_access(
            self.cfg, state, qk, qvals, sets, hit_raw, way,
            admit_on_miss, enabled, order=order, ttls=ttls,
            slot_value=slot_value)

    def put(self, state, qkeys, qvals, admit=None, enabled=None, *,
            slot_value: bool = False):
        from repro.kernels import ops
        self._require_probe_fits()
        qk, sets, present, way_present, order = ops.probe_orders(
            self.cfg, state, jnp.asarray(qkeys, jnp.uint32)
        )
        return kway.apply_put(
            self.cfg, state, qk, qvals, sets, present, way_present, order,
            admit, enabled, slot_value=slot_value,
        )

    def peek_victims(self, state, qkeys):
        from repro.kernels import ops
        self._require_probe_fits()
        _, _, hit, _, _, vkey = ops.probe(self.cfg, state,
                                          jnp.asarray(qkeys, jnp.uint32))
        valid = (vkey != EMPTY_KEY) & (~hit)
        return vkey, valid

    # -- trace-resident replay (DESIGN.md §10) -----------------------------
    def resident_fits(self) -> bool:
        """True when the replay megakernel's VMEM-resident footprint fits
        the budget: input + working copies of the 5 state lanes at the
        128-lane padded width (streams and sketch are noise next to them)."""
        from repro.kernels import kway_probe as _kp
        lane_bytes = self.cfg.num_sets * _kp.LANES * 4
        return 2 * 5 * lane_bytes <= RESIDENT_VMEM_BUDGET

    def hier_fits(self, hierarchy) -> bool:
        """True when the HIERARCHICAL megakernel's VMEM-resident footprint
        (the five L1 lanes, padded and double-buffered — same accounting as
        ``resident_fits`` with ``l1_sets`` in place of ``num_sets``) fits
        the budget.  The L2 stays in slow memory and does not count."""
        from repro.core.hierarchy import hier_footprint_bytes
        return hier_footprint_bytes(hierarchy) <= RESIDENT_VMEM_BUDGET

    def replay(self, state, chunks, enabled, tinylfu=None, sketch=None,
               hierarchy=None, ttls=None, two_phase=False):
        """Trace-resident replay, its path picked from the arguments and
        the VMEM fit (DESIGN.md §14):

          1. ``hierarchy`` configured (``l1_sets > 0``) → the hierarchical
             megakernel: L1 pinned in VMEM, L2 behind per-set row DMAs —
             near-resident throughput at capacities far past the flat
             budget.  If even the L1 exceeds the budget, the L1 tier is
             abandoned (``l1_demotion`` event) and the jnp twin runs.
          2. ``two_phase`` → ``replay_scan``: the megakernel is the fused
             access path only.
          3. flat state fits (``resident_fits``) → the flat megakernel: ALL
             lanes pinned in VMEM, bit-identical to ``replay_scan``.
          4. otherwise → the chunked-scan replay through the probe
             kernels (``vmem_budget`` event; the hierarchical mode is named
             in the event detail as the faster opt-in), or a ``ValueError``
             with the numbers when even the probe kernels cannot hold the
             cache (``probe_fits``).
        """
        from repro.kernels import ops
        hierarchy = _check_replay(tinylfu, hierarchy, ttls, two_phase)
        if hierarchy is not None:
            from repro.core import hierarchy as hier_mod
            hst = hier_mod.as_hier_state(self.cfg, hierarchy, state,
                                         ttl=ttls is not None)
            if self.hier_fits(hierarchy):
                return ops.replay_hierarchical(self.cfg, hierarchy, hst,
                                               chunks, enabled, ttls=ttls)
            from repro.robust import events
            events.record(
                component="pallas.replay", reason="l1_demotion",
                fallback_from="pallas-resident-l1l2",
                fallback_to="jnp-l1l2-scan",
                detail=(f"L1 footprint "
                        f"{hier_mod.hier_footprint_bytes(hierarchy)} B "
                        f"exceeds budget {RESIDENT_VMEM_BUDGET} B "
                        f"(l1_sets={hierarchy.l1_sets}); hierarchy "
                        f"demoted to the jnp l1_over_l2 twin"))
            return hier_mod.replay_l1_over_l2(self.cfg, hierarchy, hst,
                                              chunks, enabled, ttls=ttls)
        if two_phase:
            return self.replay_scan(state, chunks, enabled, tinylfu=tinylfu,
                                    sketch=sketch, two_phase=True)
        if not self.resident_fits():
            self._require_probe_fits()
            from repro.robust import events
            lane_bytes = self.cfg.num_sets * 128 * 4
            events.record(
                component="pallas.replay", reason="vmem_budget",
                fallback_from="pallas-resident", fallback_to="chunked-scan",
                detail=(f"resident footprint {2 * 5 * lane_bytes} B exceeds "
                        f"budget {RESIDENT_VMEM_BUDGET} B "
                        f"(num_sets={self.cfg.num_sets}); falling back to "
                        f"chunked-scan — the hierarchical resident mode "
                        f"(HierarchyConfig(l1_sets>0)) keeps a VMEM L1 over "
                        f"the HBM L2 at this capacity"))
            return self.replay_scan(state, chunks, enabled,
                                    tinylfu=tinylfu, sketch=sketch,
                                    ttls=ttls)
        return ops.replay_resident(self.cfg, state, chunks, enabled,
                                   tinylfu=tinylfu, sketch=sketch, ttls=ttls)


@register_backend("ref")
class RefBackend(CacheBackend):
    """Sequential Python oracle behind the same functional API.

    Each call imports the KWayState into a RefKWay, replays the batch one
    lane at a time (phase order matches the batched implementations: a
    disabled lane still consumes a logical timestamp), and exports back.
    Intended for differential testing, not throughput — and being host
    Python, it cannot run under jit/vmap/shard_map (traceable=False).
    """

    traceable = False

    def _import(self, state: KWayState) -> RefKWay:
        cfg = self.cfg
        ref = RefKWay(cfg.num_sets, cfg.ways, cfg.policy, cfg.seed)
        keys = np.asarray(state.keys)
        vals = np.asarray(state.vals)
        ma = np.asarray(state.meta_a)
        mb = np.asarray(state.meta_b)
        exp = None if state.expiry is None else np.asarray(state.expiry)
        empty = int(EMPTY_KEY)
        for s in range(cfg.num_sets):
            for w in range(cfg.ways):
                if int(keys[s, w]) != empty:
                    node = {
                        "key": int(keys[s, w]), "val": int(vals[s, w]),
                        "a": int(ma[s, w]), "b": int(mb[s, w]),
                    }
                    if exp is not None:
                        node["exp"] = int(exp[s, w])
                    ref.sets[s][w] = node
        ref.clock = int(state.clock)
        # _export mirrors the lane back out only when the incoming state
        # carried one — TTL-disabled states round-trip without it.
        ref.expiry_enabled = exp is not None
        return ref

    def _export(self, ref: RefKWay) -> KWayState:
        cfg = self.cfg
        keys = np.full((cfg.num_sets, cfg.ways), int(EMPTY_KEY), np.uint32)
        vals = np.zeros((cfg.num_sets, cfg.ways), np.int32)
        ma = np.zeros((cfg.num_sets, cfg.ways), np.int32)
        mb = np.zeros((cfg.num_sets, cfg.ways), np.int32)
        has_exp = getattr(ref, "expiry_enabled", False)
        exp = (np.full((cfg.num_sets, cfg.ways), kway.NO_EXPIRY, np.int32)
               if has_exp else None)
        for s in range(cfg.num_sets):
            for w, node in enumerate(ref.sets[s]):
                if node is not None:
                    keys[s, w] = node["key"]
                    vals[s, w] = node["val"]
                    ma[s, w] = node["a"]
                    mb[s, w] = node["b"]
                    if exp is not None:
                        exp[s, w] = node.get("exp", kway.NO_EXPIRY)
        keys_j = jnp.asarray(keys)
        fpr = jnp.where(keys_j == EMPTY_KEY, jnp.uint32(0),
                        hashing.fingerprint(keys_j))
        return KWayState(
            keys=keys_j, fprint=fpr, vals=jnp.asarray(vals),
            meta_a=jnp.asarray(ma), meta_b=jnp.asarray(mb),
            clock=jnp.asarray(ref.clock, jnp.int32),
            expiry=None if exp is None else jnp.asarray(exp),
        )

    @staticmethod
    def _lanes(qkeys, enabled):
        ks = [int(k) for k in np.asarray(qkeys, np.uint32)]
        # sanitize_keys: the EMPTY_KEY sentinel folds onto 0xFFFFFFFE
        ks = [0xFFFFFFFE if k == 0xFFFFFFFF else k for k in ks]
        en = (np.ones(len(ks), bool) if enabled is None
              else np.asarray(enabled, bool))
        return ks, en

    def get(self, state, qkeys, enabled=None):
        ref = self._import(state)
        ks, en = self._lanes(qkeys, enabled)
        hit = np.zeros(len(ks), bool)
        vals = np.full(len(ks), -1, np.int32)
        for i, k in enumerate(ks):
            if not en[i]:
                ref.clock += 1  # disabled lane still consumes a timestamp
                continue
            v = ref.get(k)
            if v is not None:
                hit[i], vals[i] = True, v
        return self._export(ref), jnp.asarray(hit), jnp.asarray(vals)

    def put(self, state, qkeys, qvals, admit=None, enabled=None, *,
            slot_value: bool = False):
        ref = self._import(state)
        ks, en = self._lanes(qkeys, enabled)
        vs = np.asarray(qvals, np.int32)
        ad = (np.ones(len(ks), bool) if admit is None
              else np.asarray(admit, bool))
        b = len(ks)
        ek = np.zeros(b, np.uint32)
        ev = np.zeros(b, bool)
        slot_sets = np.full(b, -1, np.int32)
        slot_ways = np.full(b, -1, np.int32)
        for i, k in enumerate(ks):
            if not en[i]:
                ref.clock += 1
                continue
            evicted, s, w = ref.put(k, int(vs[i]), admit=bool(ad[i]))
            if w is not None:
                slot_sets[i], slot_ways[i] = s, w
                if slot_value:
                    ref.sets[s][w]["val"] = s * self.cfg.ways + w
                if getattr(ref, "expiry_enabled", False):
                    # parity with kway.apply_put: a bare put has no TTL
                    # argument, so the landing lane is marked never-expiring
                    ref.sets[s][w]["exp"] = int(kway.NO_EXPIRY)
            if evicted is not None:
                ek[i], ev[i] = evicted, True
        return (self._export(ref), jnp.asarray(ek), jnp.asarray(ev),
                jnp.asarray(slot_sets), jnp.asarray(slot_ways))

    def peek_victims(self, state, qkeys):
        ref = self._import(state)
        ks, _ = self._lanes(qkeys, None)
        clock0 = ref.clock
        vk = np.zeros(len(ks), np.uint32)
        vv = np.zeros(len(ks), bool)
        for i, k in enumerate(ks):
            ref.clock = clock0 + i   # lane i probes at logical time clock+i
            victim = ref.peek_victim(k)
            if victim is not None:
                vk[i], vv[i] = victim, True
        ref.clock = clock0
        return jnp.asarray(vk), jnp.asarray(vv)

    @_access_span
    def access(self, state, qkeys, qvals, admit_on_miss=None, enabled=None,
               ttls=None, *, slot_value: bool = False):
        """Oracle access with the same expiry discipline as the batched
        paths (DESIGN.md §15): scrub lanes whose deadline falls at or before
        the batch-exit clock BEFORE probing (so an expired key can never be
        served), then two-phase get/put, then stamp landed lanes with
        ``clock0 + 2B + ttl`` (``ttl <= 0`` = never expires)."""
        if state.expiry is not None:
            b = int(np.asarray(qkeys).shape[0])
            state = kway.scrub_expired(state, state.clock + jnp.int32(2 * b))
        if ttls is None:
            return self.access_two_phase(
                state, qkeys, qvals, admit_on_miss=admit_on_miss,
                enabled=enabled, slot_value=slot_value)
        if state.expiry is None:
            raise ValueError(
                "ref access: ttls given but the state has no expiry lane — "
                "build it with make_cache(cfg, ttl=True) or ensure_expiry()")
        clock0 = int(state.clock)
        b = int(np.asarray(qkeys).shape[0])
        state, hit, vals = self.get(state, qkeys, enabled=enabled)
        en = (~hit) if enabled is None else (jnp.asarray(enabled) & ~hit)
        state, ek, ev, ss, sw = self.put(
            state, qkeys, qvals, admit=admit_on_miss, enabled=en,
            slot_value=slot_value)
        # deadline-stamp the lanes the put phase landed (ss/sw == -1 where
        # the key did not land); matches kway.insert_deadlines bit-for-bit
        tt = np.asarray(ttls, np.int32)
        exp = np.asarray(state.expiry).copy()
        ssn = np.asarray(ss)
        swn = np.asarray(sw)
        for i in range(b):
            if ssn[i] >= 0:
                exp[ssn[i], swn[i]] = (
                    clock0 + 2 * b + int(tt[i]) if tt[i] > 0
                    else int(kway.NO_EXPIRY))
        state = dataclasses.replace(state, expiry=jnp.asarray(exp))
        if slot_value:
            slot_id = ss * jnp.int32(self.cfg.ways) + sw
            vals = jnp.where(hit, vals, jnp.where(ss >= 0, slot_id, -1))
        else:
            vals = jnp.where(hit, vals, qvals)
        return state, hit, vals, ek, ev

    def replay(self, state, chunks, enabled, tinylfu=None, sketch=None,
               hierarchy=None, ttls=None, two_phase=False):
        """The oracle's replay: a host loop over the chunks, each through
        ``access`` (``access_two_phase`` when ``two_phase``) with its TTLs
        when given.  Same contract as ``CacheBackend.replay`` and the same
        results at B = 1; the flat replay only, without admission."""
        if tinylfu is not None:
            raise ValueError("TinyLFU replay is not wired for the ref backend")
        if _check_replay(None, hierarchy, ttls, two_phase) is not None:
            raise ValueError(
                "hierarchical replay needs a traceable backend "
                "('jnp' or 'pallas'); the ref oracle is flat-only")
        if ttls is not None:
            state = kway.ensure_expiry(state)
            ttls = np.asarray(ttls, np.int32)
        access = self.access_two_phase if two_phase else self.access
        chunks = np.asarray(chunks, np.uint32)
        enabled = np.asarray(enabled, bool)
        hits, evs = [], []
        for i in range(chunks.shape[0]):
            keys = jnp.asarray(chunks[i])
            ttl_kw = {} if ttls is None else {"ttls": ttls[i]}
            state, hit, _, _, ev = access(
                state, keys, keys.astype(jnp.int32), None,
                jnp.asarray(enabled[i]), **ttl_kw)
            hits.append(int(np.asarray(hit).sum()))
            evs.append(int(np.asarray(ev).sum()))
        return (jnp.asarray(hits, jnp.int32), jnp.asarray(evs, jnp.int32),
                state, None)

    replay_scan = replay          # the oracle's one replay is its host loop
