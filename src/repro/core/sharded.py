"""Set-sharded execution: the paper's "Alice and Bob never synchronize"
parallelism, with the request router resident on device (DESIGN.md §5, §9).

Sets are data-independent, so a global cache of S sets splits into D
sub-caches of S/D sets with zero cross-shard traffic.  The only cross-shard
work is routing query keys to the shard owning their set — and since PR 4
that routing is traceable jnp (core/router.py): owner = high bits of the
global set index, one stable argsort into a fixed ``[D, capacity]`` bucket
layout, inverse-permutation unscatter.  Routing therefore lives *inside*
jit — an entire chunked trace replays in ONE ``lax.scan`` (route →
vmap/shard_map fused access → unscatter per step) with the shard states
donated across steps, instead of the old per-chunk numpy bucketing with a
device↔host round trip per batch.

Execution modes:
  * ``mesh`` given — ``shard_map`` over the set axis; compiles to zero
    collectives in the cache step (verified by tests/test_kway_sharding.py);
    the router runs replicated (its inputs are the whole batch).
  * no mesh (default) — a ``vmap`` over the shard axis on one device: the
    same math, used as the single-device fallback and for CPU benchmarking.

Admission composes with sharding by privatization ("Flexible Support for
Fast Parallel Commutative Updates"): the TinyLFU sketch is stacked per shard
(leaves [D, …]) and record/peek/admit run inside the shard body on the
shard's own stream — each shard admits on its local frequency view, which
tracks the global sketch closely (tests bound the hit-ratio gap) without a
single shared-counter synchronization point.

Because every request of one set lands in the same shard bucket with its
arrival order preserved, the batched conflict resolution inside each shard
matches the unsharded cache request-for-request: hits, evictions, and final
keys/vals are identical for the timestamp-order-invariant policies
(LRU / LFU / FIFO).  RANDOM and HYPERBOLIC score on absolute clock values,
which shard-local clocks shift, so they are statistically — not bitwise —
equivalent.

Overflow-defer: with ``route_capacity`` below the batch size, lanes ranked
past a bucket's capacity are *deferred* — not processed, never silently
dropped: ``access(..., return_deferred=True)`` reports the mask, ``replay``
counts them (as misses) and returns the total.  The default capacity equals
the batch size, which can never overflow.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import admission, router
from repro.core.admission import TinyLFUConfig, TinyLFUState
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig, KWayState

# Trace-time side effect (same pattern as repro/eval/runner.py): each jitted
# body bumps its key once per XLA compilation, so tests can assert the fixed
# [D, capacity] router layout really is shape-stable — ≤ 1 compile per
# (op, shape) — instead of recompiling per batch like the old counts.max()
# bucketing did.
_TRACE_COUNTS: collections.Counter = collections.Counter()


def trace_counts() -> dict:
    """Compilation tally of the sharded kernels, keyed by (op, shape...)."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


@dataclasses.dataclass(frozen=True)
class ShardedConfig:
    """Global cache shape + how to split its set axis."""

    cache: KWayConfig            # GLOBAL shape: cache.num_sets across all shards
    num_shards: int = 1
    backend: str = "jnp"
    # Donate the stacked state leaves to the jitted shard step so each batch
    # updates the [D, S/D, k] lanes in place instead of copying them.  The
    # caller must treat the state passed to ``access`` as consumed (rebind
    # the returned one) — which is how every replay loop already uses it.
    donate: bool = False
    # Router bucket capacity (requests per shard per step).  None — the
    # default — means "the batch size", which can never overflow.  Smaller
    # values shrink the padded [D, capacity] layout; overflow lanes are
    # deferred (reported, not dropped) — see the module docstring.
    route_capacity: Optional[int] = None

    def __post_init__(self):
        assert self.num_shards >= 1
        assert self.num_shards & (self.num_shards - 1) == 0, \
            "num_shards must be a power of two (it splits the set-index bits)"
        assert self.cache.num_sets % self.num_shards == 0 and \
            self.cache.num_sets >= self.num_shards
        assert self.route_capacity is None or self.route_capacity >= 1

    @property
    def local(self) -> KWayConfig:
        """Per-shard cache config: same ways/policy, S/D sets."""
        return dataclasses.replace(
            self.cache, num_sets=self.cache.num_sets // self.num_shards
        )

    def capacity_for(self, batch: int) -> int:
        return batch if self.route_capacity is None else self.route_capacity


class ShardedCache:
    """A K-way cache whose set axis is sharded D ways.

    The state is the per-shard ``KWayState`` stacked on a leading shard axis
    (leaves [D, S/D, k]; clock [D]).  All public operations route on device:
    ``access``/``get``/``put``/``peek_victims`` are one jitted call each
    (router + per-shard op + unscatter), and ``replay`` runs a whole chunked
    trace in a single ``lax.scan``.

    ``get``/``put`` follow the CacheBackend contract closely enough for
    serve/engine.py to use a ShardedCache as its prefix-cache backend:
    ``put(slot_value=True)`` stores and reports *global* slot ids
    (``global_set * ways + way`` with ``global_set = d * S/D + local_set``).
    """

    def __init__(self, cfg: ShardedConfig, mesh=None):
        self.cfg = cfg
        self.backend = make_backend(cfg.backend, cfg.local)
        if not self.backend.traceable:
            raise ValueError(
                f"backend {cfg.backend!r} is host Python and cannot run "
                "under vmap/shard_map; shard the 'jnp' or 'pallas' backend")
        self.mesh = mesh
        if mesh is not None:
            if "sets" not in mesh.axis_names or \
                    mesh.shape["sets"] != cfg.num_shards:
                raise ValueError(
                    "mesh must carry a 'sets' axis of exactly num_shards "
                    f"devices (one shard per device); got axes "
                    f"{dict(mesh.shape)} for num_shards={cfg.num_shards}")
            kind = dict(zip(mesh.axis_names, mesh.axis_types))["sets"]
            if kind != jax.sharding.AxisType.Auto:
                # the replicated router gathers the per-shard outputs back
                # into request order; under an Explicit axis JAX asks for an
                # out_sharding on every such gather instead of inserting it
                raise ValueError(
                    "the mesh's 'sets' axis must be AxisType.Auto "
                    "(jax.make_mesh(..., axis_types=(AxisType.Auto,))); "
                    f"got {kind}")
        self._fns: dict = {}   # (kind, *statics) -> jitted callable

    # ------------------------------------------------------------- plumbing
    def init(self, *, ttl: bool = False) -> KWayState:
        """Empty stacked state (leaves [D, S/D, k]).  With a mesh, each
        shard's lanes are built on the device that owns them (sharded on
        the ``sets`` axis), never gathered on one device first."""
        d = self.cfg.num_shards

        def build():
            st = self.backend.init(ttl=ttl)
            stack = lambda l: jnp.tile(  # noqa: E731
                l[None], (d,) + (1,) * l.ndim)
            leaves = [stack(l) for l in
                      (st.keys, st.fprint, st.vals, st.meta_a, st.meta_b)]
            return KWayState(
                *leaves, clock=jnp.zeros((d,), jnp.int32),
                expiry=stack(st.expiry) if st.expiry is not None else None)

        if self.mesh is None:
            return build()
        return jax.jit(build, out_shardings=self._sharding("sets"))()

    def _sharding(self, *spec):
        """``NamedSharding`` on this cache's mesh (``()`` = replicated)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(*spec))

    def init_sketches(self, tinylfu: TinyLFUConfig) -> TinyLFUState:
        """Per-shard TinyLFU sketches, stacked on the shard axis [D, …]."""
        d = self.cfg.num_shards
        return jax.vmap(lambda _: admission.make_sketch(tinylfu))(
            jnp.arange(d))

    def owner_of(self, keys) -> np.ndarray:
        """Owning shard per key: the high bits of the global set index."""
        return np.asarray(router.owner_of(
            jnp.asarray(keys, jnp.uint32), self.cfg.cache.num_sets,
            self.cfg.num_shards, self.cfg.cache.seed))

    def _route(self, keys, enabled, capacity):
        owner = router.owner_of(keys, self.cfg.cache.num_sets,
                                self.cfg.num_shards, self.cfg.cache.seed)
        return router.route(owner, self.cfg.num_shards, capacity, enabled)

    def _local_access(self, tinylfu, two_phase, shard_idx, keys, vals, en,
                      sketch, state: KWayState, ttls=None):
        """One shard's step on its own bucket ([capacity] lanes).

        Runs the TinyLFU record→peek→admit phases on the shard's private
        sketch (same phase order as the unsharded batched replay), then the
        fused access — or the two-phase oracle when ``two_phase``.
        ``ttls`` (int32 [capacity], optional) are the bucketed per-request
        TTLs; deadlines are chunk-constant (``clock + 2·capacity + ttl``),
        so bucketing's lane permutation cannot perturb them.
        """
        del shard_idx
        be = self.backend
        admit = None
        if tinylfu is not None:
            sketch = admission.record(tinylfu, sketch, keys, enabled=en)
            vkeys, vvalid = be.peek_victims(state, keys)
            admit = admission.admit(tinylfu, sketch, keys, vkeys, vvalid)
        if two_phase:
            state, hit, out, ek, ev = be.access_two_phase(
                state, keys, vals, admit, en)
        else:
            kw = {} if ttls is None else {"ttls": ttls}
            state, hit, out, ek, ev = be.access(
                state, keys, vals, admit, en, **kw)
        return state, sketch, hit, out, ek, ev

    def _bucketed(self, plan, keys, vals, capacity):
        d = self.cfg.num_shards
        kb = router.bucket(plan, keys, d, capacity, jnp.uint32(0))
        vb = router.bucket(plan, vals, d, capacity, jnp.int32(0))
        eb = router.bucket_mask(plan, d, capacity)
        return kb, vb, eb

    def _shard_call(self, body, args_bucketed, state, sketch):
        """Run ``body`` once per shard over bucketed args: ``vmap`` on one
        device, ``shard_map`` over the mesh's set axis otherwise."""
        d = self.cfg.num_shards
        shard_ids = jnp.arange(d, dtype=jnp.int32)
        if self.mesh is None:
            return jax.vmap(body)(shard_ids, *args_bucketed, sketch, state)
        from jax.sharding import PartitionSpec as P

        def dev(body_args):
            out = body(*jax.tree_util.tree_map(lambda x: x[0], body_args))
            return jax.tree_util.tree_map(lambda x: x[None], out)

        sharded = jax.shard_map(
            lambda *a: dev(a), mesh=self.mesh,
            in_specs=jax.tree_util.tree_map(
                lambda _: P("sets"), (shard_ids,) + tuple(args_bucketed)
                + (sketch, state)),
            out_specs=P("sets"))
        return sharded(shard_ids, *args_bucketed, sketch, state)

    def _step(self, tinylfu, two_phase, keys, vals, enabled, state, sketch,
              capacity):
        """Route one batch, run every shard, unscatter.  Fully traceable.

        Returns (state', sketch', hit[B], out[B], ek[B], ev[B], deferred[B])
        in original request order.
        """
        plan = self._route(keys, enabled, capacity)
        kb, vb, eb = self._bucketed(plan, keys, vals, capacity)

        def body(shard_idx, k, v, e, sk, st):
            st2, sk2, hit, out, ek, ev = self._local_access(
                tinylfu, two_phase, shard_idx, k, v, e, sk, st)
            return st2, sk2, hit, out, ek, ev

        state, sketch, hit_b, out_b, ek_b, ev_b = self._shard_call(
            body, (kb, vb, eb), state, sketch)
        hit = router.unscatter(plan, hit_b, False)
        out = router.unscatter(plan, out_b, jnp.int32(-1))
        ek = router.unscatter(plan, ek_b, jnp.uint32(0))
        ev = router.unscatter(plan, ev_b, False)
        return state, sketch, hit, out, ek, ev, plan.deferred

    # ------------------------------------------------------------------ API
    def access(self, state: KWayState, keys, vals, *, tinylfu=None,
               sketches=None, two_phase=False, return_deferred=False):
        """Batched get-or-insert across all shards — one jitted call
        (device-resident routing; no host bucketing).

        Returns (state', hit[B], vals[B], evicted_keys[B], evicted_valid[B])
        in the original request order; with ``return_deferred=True`` the
        overflow-defer mask is appended.  With ``tinylfu`` the per-shard
        ``sketches`` (``init_sketches``) ride along and the updated stack is
        appended to the return.
        """
        keys = jnp.asarray(np.asarray(keys, np.uint32))
        vals = jnp.asarray(np.asarray(vals, np.int32))
        b = keys.shape[0]
        capacity = self.cfg.capacity_for(b)
        fkey = ("step", tinylfu, two_phase, capacity)
        if fkey not in self._fns:
            def fn(keys, vals, state, sketch, _tl=tinylfu, _tp=two_phase,
                   _cap=capacity):
                _TRACE_COUNTS[("step", self.cfg.backend,
                               self.cfg.num_shards, self.cfg.local.num_sets,
                               self.cfg.cache.ways, _cap, keys.shape[0],
                               _tl is not None, _tp)] += 1
                en = jnp.ones(keys.shape, jnp.bool_)
                st, sk, hit, out, ek, ev, defer = self._step(
                    _tl, _tp, keys, vals, en, state, sketch, _cap)
                return st, sk, hit, out, ek, ev, defer
            donate = (2, 3) if self.cfg.donate else ()
            self._fns[fkey] = jax.jit(fn, donate_argnums=donate)
        sketch_in = (sketches if sketches is not None
                     else jnp.zeros((self.cfg.num_shards,), jnp.int32))
        st, sk, hit, out, ek, ev, defer = self._fns[fkey](
            keys, vals, state, sketch_in)
        ret = (st, hit, out, ek, ev)
        if return_deferred:
            ret = ret + (defer,)
        if tinylfu is not None:
            ret = ret + (sk,)
        return ret

    def _bucket_all(self, chunks, en, capacity: int, tt=None):
        """Route EVERY chunk of a replay up front — one jitted call.

        Returns (kb uint32 [D, steps, capacity], eb bool [D, steps,
        capacity], tb int32 [D, steps, capacity] | None, deferred int32
        scalar): per-shard request streams in the exact per-chunk bucket
        layout the scanned replay routes step by step, transposed
        shard-major so each shard's whole trace is one contiguous
        [steps, capacity] stream (what ``CacheBackend.replay`` consumes).
        ``tb`` carries the per-request TTLs when ``tt`` is given.
        """
        fkey = ("bucket_all", capacity, chunks.shape, tt is not None)
        if fkey not in self._fns:
            def fn(chunks, en, tt, _cap=capacity):
                _TRACE_COUNTS[("bucket_all", self.cfg.backend,
                               self.cfg.num_shards, _cap,
                               chunks.shape[1])] += 1

                def per_chunk(keys, e, t):
                    plan = self._route(keys, e, _cap)
                    kb = router.bucket(plan, keys, self.cfg.num_shards,
                                       _cap, jnp.uint32(0))
                    eb = router.bucket_mask(plan, self.cfg.num_shards, _cap)
                    tb = (None if t is None else
                          router.bucket(plan, t, self.cfg.num_shards, _cap,
                                        jnp.int32(0)))
                    return kb, eb, tb, jnp.sum(plan.deferred, dtype=jnp.int32)

                kb, eb, tb, defer = jax.vmap(per_chunk)(chunks, en, tt)
                tr = lambda a: a.transpose(1, 0, 2)  # noqa: E731
                return (tr(kb), tr(eb), None if tb is None else tr(tb),
                        jnp.sum(defer))
            self._fns[fkey] = jax.jit(fn)
        return self._fns[fkey](chunks, en, tt)

    def _replay_resident(self, chunks, en, capacity, tinylfu, state,
                         hierarchy=None, ttls=None):
        """Resident replay: route all chunks once, then ONE megakernel (or
        scanned replay, for the jnp backend) per shard — D launches for the
        whole trace instead of D×steps, with each shard's five state lanes
        and TinyLFU sketch pinned in VMEM for the duration (DESIGN.md §10).

        Bit-identical to the scanned path: the per-chunk bucket streams are
        routed by the same ``router.route``, and ``CacheBackend.replay``
        applies the same fused access + admission phases per chunk.

        ``hierarchy`` threads the L1-over-L2 mode (DESIGN.md §14) through
        each shard's replay: every shard gets its OWN private L1 (attached
        fresh by ``CacheBackend.replay`` when the shard state is a bare
        ``KWayState``) while the L2 remains the sharded global state — the
        returned stacked state is a ``HierState`` of per-shard tiers.
        """
        d = self.cfg.num_shards
        kb, eb, tb, defers = self._bucket_all(chunks, en, capacity, ttls)
        sketches = (self.init_sketches(tinylfu) if tinylfu is not None
                    else None)
        hits = 0
        shard_states = []
        for i in range(d):
            st_i = jax.tree_util.tree_map(lambda l: l[i], state)
            sk_i = (jax.tree_util.tree_map(lambda l: l[i], sketches)
                    if tinylfu is not None else None)
            h, _, st_i, _ = self.backend.replay(
                st_i, kb[i], eb[i], tinylfu=tinylfu, sketch=sk_i,
                hierarchy=hierarchy,
                ttls=None if tb is None else tb[i])
            hits += int(jnp.sum(h))
            shard_states.append(st_i)
        stacked = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *shard_states)
        return hits, int(defers), stacked

    def replay(self, trace, batch: int, *, tinylfu=None, two_phase=False,
               state: Optional[KWayState] = None, resident: bool = False,
               hierarchy=None, ttls=None):
        """Replay a whole trace in ONE jitted ``lax.scan`` — route, shard
        access and hit accounting all on device; the only host transfers are
        the trace in and three scalars out.

        The tail chunk is padded with disabled lanes, so every request of
        the trace is replayed.  Returns (hits, deferred, state'): ``hits``
        counts over the full trace, ``deferred`` counts overflow-deferred
        lanes (0 under the default capacity — deferred lanes are the only
        requests not replayed, and they are reported, not dropped).

        The initial ``state`` (default ``init()``) is donated to the scan:
        shard states update in place across all steps.

        ``resident=True`` routes every chunk up front and hands each shard
        its whole stream in one ``CacheBackend.replay`` call — on the
        pallas backend D trace-resident megakernel launches for the entire
        replay (see ``_replay_resident``).  Excludes ``two_phase`` (the
        resident path is the fused access) and mesh execution (the host
        drives one launch per shard).

        ``ttls`` (int array [len(trace)], optional) gives each request a
        time-to-live on the logical clock (DESIGN.md §15).  Deadlines are
        chunk-constant (``clock + 2·capacity + ttl``) and shard-local
        clocks track the global clock at chunk boundaries, so the sharded
        expiry replay stays bit-identical to the unsharded one.  Excludes
        ``two_phase`` and ``tinylfu``.
        """
        trace = np.asarray(trace, np.uint32)
        chunks, en = router.pad_chunks(trace, batch)
        if self.mesh is None:
            chunks, en = jnp.asarray(chunks), jnp.asarray(en)
        else:
            # the router runs replicated: every device sees the whole batch
            chunks, en = jax.device_put((chunks, en), self._sharding())
        capacity = self.cfg.capacity_for(batch)
        if ttls is not None:
            if two_phase:
                raise ValueError(
                    "per-request TTLs run on the fused access path; "
                    "two_phase has no expiry semantics")
            if tinylfu is not None:
                raise ValueError(
                    "per-request TTLs and TinyLFU admission are mutually "
                    "exclusive (the sketch has no expiry-aware semantics)")
            if len(np.asarray(ttls)) != len(trace):
                raise ValueError(
                    f"ttls length {len(np.asarray(ttls))} != trace length "
                    f"{len(trace)}")
            tt = np.zeros(chunks.shape, np.int32)
            tt.reshape(-1)[: len(trace)] = np.asarray(ttls, np.int32)
            tt = jnp.asarray(tt)
        else:
            tt = None

        if hierarchy is not None and hierarchy.enabled and not resident:
            raise ValueError(
                "sharded hierarchical replay runs per-shard megakernels; "
                "pass resident=True")
        if resident:
            if two_phase:
                raise ValueError(
                    "resident replay is the fused access path; two_phase "
                    "is the chunked-scan oracle — use resident=False")
            if self.mesh is not None:
                raise ValueError(
                    "resident replay drives one megakernel per shard from "
                    "the host; run mesh execution through the scanned path")
            if hierarchy is not None and hierarchy.enabled and \
                    tinylfu is not None:
                raise ValueError(
                    "hierarchical replay does not support TinyLFU admission")
            return self._replay_resident(
                chunks, en, capacity, tinylfu,
                state if state is not None
                else self.init(ttl=tt is not None),
                hierarchy=hierarchy, ttls=tt)

        fkey = ("replay", tinylfu, two_phase, capacity, batch,
                tt is not None)
        if fkey not in self._fns:
            def fn(chunks, en, tt, state, sketch, _tl=tinylfu,
                   _tp=two_phase, _cap=capacity, _ttl=tt is not None):
                _TRACE_COUNTS[("replay", self.cfg.backend,
                               self.cfg.num_shards, self.cfg.local.num_sets,
                               self.cfg.cache.ways, _cap, chunks.shape[1],
                               _tl is not None, _tp)] += 1

                def scan_step(carry, xs):
                    st, sk, hits, defers = carry
                    if _ttl:
                        keys, e, t = xs
                    else:
                        keys, e = xs
                    plan = self._route(keys, e, _cap)
                    kb, vb, eb = self._bucketed(
                        plan, keys, keys.astype(jnp.int32), _cap)
                    if _ttl:
                        tb = router.bucket(plan, t, self.cfg.num_shards,
                                           _cap, jnp.int32(0))

                        def body(shard_idx, k, v, e2, t2, sk1, st1):
                            st2, sk2, hit, out, ek, ev = self._local_access(
                                _tl, _tp, shard_idx, k, v, e2, sk1, st1,
                                ttls=t2)
                            return st2, sk2, jnp.sum(hit & e2,
                                                     dtype=jnp.int32)

                        args = (kb, vb, eb, tb)
                    else:
                        def body(shard_idx, k, v, e2, sk1, st1):
                            st2, sk2, hit, out, ek, ev = self._local_access(
                                _tl, _tp, shard_idx, k, v, e2, sk1, st1)
                            # hit counting happens pre-unscatter: summing
                            # the bucketed lanes equals summing the request
                            # lanes.
                            return st2, sk2, jnp.sum(hit & e2,
                                                     dtype=jnp.int32)

                        args = (kb, vb, eb)

                    st, sk, h = self._shard_call(body, args, st, sk)
                    return (st, sk, hits + jnp.sum(h),
                            defers + jnp.sum(plan.deferred,
                                             dtype=jnp.int32)), ()

                zero = jnp.zeros((), jnp.int32)
                xs = (chunks, en, tt) if _ttl else (chunks, en)
                (st, sk, hits, defers), _ = jax.lax.scan(
                    scan_step, (state, sketch, zero, zero), xs)
                return hits, defers, st, sk
            self._fns[fkey] = jax.jit(fn, donate_argnums=(3, 4))
        if state is None:
            state = self.init(ttl=tt is not None)
        sketch = (self.init_sketches(tinylfu) if tinylfu is not None
                  else jnp.zeros((self.cfg.num_shards,), jnp.int32))
        hits, defers, st, _ = self._fns[fkey](chunks, en, tt, state, sketch)
        return int(hits), int(defers), st

    # ----------------------------------------------- CacheBackend-ish ops
    # (the serve engine's prefix cache drives these; slot ids are global)
    def get(self, state: KWayState, qkeys, enabled=None):
        qkeys = jnp.asarray(np.asarray(qkeys, np.uint32))
        b = qkeys.shape[0]
        capacity = self.cfg.capacity_for(b)
        fkey = ("get", capacity)
        if fkey not in self._fns:
            def fn(qkeys, en, state, _cap=capacity):
                _TRACE_COUNTS[("get", self.cfg.backend, self.cfg.num_shards,
                               self.cfg.local.num_sets, self.cfg.cache.ways,
                               _cap, qkeys.shape[0])] += 1
                plan = self._route(qkeys, en, _cap)
                d = self.cfg.num_shards
                kb = router.bucket(plan, qkeys, d, _cap, jnp.uint32(0))
                eb = router.bucket_mask(plan, d, _cap)

                def body(shard_idx, k, e, sk, st):
                    del shard_idx, sk
                    st, hit, vals = self.backend.get(st, k, enabled=e)
                    return st, hit, vals

                st, hit_b, val_b = self._shard_call(
                    body, (kb, eb), state,
                    jnp.zeros((d,), jnp.int32))
                hit = router.unscatter(plan, hit_b, False)
                vals = router.unscatter(plan, val_b, jnp.int32(-1))
                return st, hit, vals
            self._fns[fkey] = jax.jit(fn)
        en = (jnp.ones((b,), jnp.bool_) if enabled is None
              else jnp.asarray(enabled))
        return self._fns[fkey](qkeys, en, state)

    def put(self, state: KWayState, qkeys, qvals, admit=None, enabled=None,
            *, slot_value: bool = False):
        qkeys = jnp.asarray(np.asarray(qkeys, np.uint32))
        qvals = jnp.asarray(np.asarray(qvals, np.int32))
        b = qkeys.shape[0]
        capacity = self.cfg.capacity_for(b)
        s_local = self.cfg.local.num_sets
        ways = self.cfg.cache.ways
        fkey = ("put", capacity, slot_value)
        if fkey not in self._fns:
            def fn(qkeys, qvals, admit, en, state, _cap=capacity,
                   _sv=slot_value):
                _TRACE_COUNTS[("put", self.cfg.backend, self.cfg.num_shards,
                               self.cfg.local.num_sets, self.cfg.cache.ways,
                               _cap, qkeys.shape[0], _sv)] += 1
                plan = self._route(qkeys, en, _cap)
                d = self.cfg.num_shards
                kb = router.bucket(plan, qkeys, d, _cap, jnp.uint32(0))
                vb = router.bucket(plan, qvals, d, _cap, jnp.int32(0))
                ab = router.bucket(plan, admit, d, _cap, False)
                eb = router.bucket_mask(plan, d, _cap)

                def body(shard_idx, k, v, a, e, sk, st):
                    del sk
                    st, ek, ev, ss, sw = self.backend.put(
                        st, k, v, admit=a, enabled=e, slot_value=_sv)
                    if _sv:
                        # The local put stored local slot ids as payload;
                        # lift them to global ids in place.  Scatter-SET the
                        # recomputed global id (not scatter-ADD an offset):
                        # two active lanes may legally share a (set, way) —
                        # a present key plus an insert victimizing its way —
                        # and duplicate-index adds would apply the shard
                        # offset twice; duplicate sets of the same value are
                        # idempotent.
                        landed = ss >= 0
                        ssw = jnp.where(landed, ss, jnp.int32(s_local))
                        gval = (ss + shard_idx * jnp.int32(s_local)) \
                            * jnp.int32(ways) + sw
                        vals2 = st.vals.at[ssw, jnp.maximum(sw, 0)].set(
                            jnp.where(landed, gval, 0), mode="drop")
                        st = dataclasses.replace(st, vals=vals2)
                    gs = jnp.where(ss >= 0, ss + shard_idx * s_local, -1)
                    return st, ek, ev, gs, sw

                st, ek_b, ev_b, ss_b, sw_b = self._shard_call(
                    body, (kb, vb, ab, eb), state,
                    jnp.zeros((d,), jnp.int32))
                ek = router.unscatter(plan, ek_b, jnp.uint32(0))
                ev = router.unscatter(plan, ev_b, False)
                ss = router.unscatter(plan, ss_b, jnp.int32(-1))
                sw = router.unscatter(plan, sw_b, jnp.int32(-1))
                return st, ek, ev, ss, sw
            self._fns[fkey] = jax.jit(fn)
        en = (jnp.ones((b,), jnp.bool_) if enabled is None
              else jnp.asarray(enabled))
        ad = (jnp.ones((b,), jnp.bool_) if admit is None
              else jnp.asarray(admit))
        return self._fns[fkey](qkeys, qvals, ad, en, state)

    def peek_victims(self, state: KWayState, qkeys):
        qkeys = jnp.asarray(np.asarray(qkeys, np.uint32))
        b = qkeys.shape[0]
        capacity = self.cfg.capacity_for(b)
        fkey = ("peek", capacity)
        if fkey not in self._fns:
            def fn(qkeys, state, _cap=capacity):
                _TRACE_COUNTS[("peek", self.cfg.backend, self.cfg.num_shards,
                               self.cfg.local.num_sets, self.cfg.cache.ways,
                               _cap, qkeys.shape[0])] += 1
                en = jnp.ones(qkeys.shape, jnp.bool_)
                plan = self._route(qkeys, en, _cap)
                d = self.cfg.num_shards
                kb = router.bucket(plan, qkeys, d, _cap, jnp.uint32(0))

                def body(shard_idx, k, sk, st):
                    del shard_idx, sk
                    return self.backend.peek_victims(st, k)

                vk_b, vv_b = self._shard_call(
                    body, (kb,), state, jnp.zeros((d,), jnp.int32))
                vk = router.unscatter(plan, vk_b, jnp.uint32(0))
                vv = router.unscatter(plan, vv_b, False)
                return vk, vv
            self._fns[fkey] = jax.jit(fn)
        return self._fns[fkey](qkeys, state)

    def global_view(self, state: KWayState) -> KWayState:
        """Reassemble the stacked shard states into the equivalent global
        single-device state (sets of shard d map to global sets
        [d*S/D, (d+1)*S/D)).  Clock is summed — a diagnostic view; policy
        metadata keeps its shard-local timestamps."""
        s, k = self.cfg.cache.num_sets, self.cfg.cache.ways
        merge = lambda l: l.reshape((s, k))  # noqa: E731
        return KWayState(
            keys=merge(state.keys), fprint=merge(state.fprint),
            vals=merge(state.vals), meta_a=merge(state.meta_a),
            meta_b=merge(state.meta_b), clock=jnp.sum(state.clock),
            expiry=(merge(state.expiry) if state.expiry is not None
                    else None),
        )
