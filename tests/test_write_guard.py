"""The write guard (``kway._scatter_when``): a phase's lane scatters run
only when some lane of the batch writes in that phase.

Every output of ``apply_access``, ``apply_get`` and ``apply_put`` is held
bit for bit to the unguarded form, kept here as a copy of the two write
helpers as they were before the guard (scatters on every batch), over every
policy and the batches that take and skip each guard.  The sharded cache's
one-device ``vmap`` fallback, where the guard's batching rule runs, is held
to the unsharded cache.
"""
import contextlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashing, kway
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig, KWayState
from repro.core.policies import Policy, on_hit
from repro.core.sharded import ShardedCache, ShardedConfig

SETS, WAYS = 128, 8     # a tiled slot view (k = 8, S a multiple of 128)
BATCHES = ["all_hit", "no_hit", "mixed", "all_disabled", "dup_hit"]


# ---------------------------------------------------------------------------
# oracle: the write helpers without the guard
# ---------------------------------------------------------------------------

def _unguarded_hits(cfg, meta_a, meta_b, slot, hit, times):
    if cfg.policy in (Policy.FIFO, Policy.RANDOM):
        return meta_a
    ma_hit = meta_a[slot]
    new_a, _ = on_hit(cfg.policy, ma_hit, meta_b[slot], times)
    if cfg.policy in (Policy.LFU, Policy.HYPERBOLIC):
        return meta_a.at[slot].add(jnp.where(hit, new_a - ma_hit, 0),
                                   mode="drop")
    return meta_a.at[slot].max(jnp.where(hit, new_a, -(2**31 - 1)),
                               mode="drop")


def _unguarded_entries(v, state, meta_a, slot_w, qkeys, qvals, new_a, new_b,
                       deadline, clock):
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


@contextlib.contextmanager
def _unguarded():
    saved = kway._write_hits, kway._write_entries
    kway._write_hits, kway._write_entries = (_unguarded_hits,
                                             _unguarded_entries)
    try:
        yield
    finally:
        kway._write_hits, kway._write_entries = saved


def _oracle(fn):
    """``fn`` traced with the unguarded helpers (its own jit cache)."""
    def run(*args, **kwargs):
        with _unguarded():
            return fn.__wrapped__(*args, **kwargs)
    return jax.jit(run, static_argnums=0, static_argnames=("slot_value",))


_ORACLE = {name: _oracle(getattr(kway, name))
           for name in ("apply_access", "apply_get", "apply_put")}


# ---------------------------------------------------------------------------
# batches
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=0)
def _fill(cfg, state, keys, ttls):
    def step(st, xs):
        kk, tt = xs
        kw = {} if st.expiry is None else {"ttls": tt}
        st, *_ = kway.access(cfg, st, kk, kk.astype(jnp.int32), **kw)
        return st, None
    return jax.lax.scan(step, state, (keys, ttls))[0]


def _case(policy, expiry, b, batch, seed=0):
    """(cfg, filled state, qkeys, enabled, ttls) for one batch kind."""
    rng = np.random.default_rng(seed)
    cfg = KWayConfig(num_sets=SETS, ways=WAYS, policy=policy)
    fill = rng.permutation(1 << 20)[:512].astype(np.uint32) + 1
    ttls = rng.integers(0, 1 << 16, fill.shape).astype(np.int32)
    state = _fill(cfg, kway.make_cache(cfg, ttl=expiry),
                  jnp.asarray(fill.reshape(4, 128)),
                  jnp.asarray(ttls.reshape(4, 128)))
    present = np.asarray(state.keys).ravel()
    present = present[present != hashing.EMPTY_KEY]
    absent = (rng.permutation(1 << 20)[:b].astype(np.uint32)
              + np.uint32(1 << 21))
    hits = rng.choice(present, b)
    enabled = np.ones(b, bool)
    if batch in ("all_hit", "dup_hit"):
        keys = hits
        if batch == "dup_hit":
            keys[-1] = keys[0]
    elif batch == "no_hit":
        keys = absent
    else:
        keys = np.where(np.arange(b) % 2 == 0, hits, absent)
        if batch == "all_disabled":
            enabled[:] = False
    qttls = jnp.asarray(rng.integers(-2, 1 << 12, b).astype(np.int32))
    return (cfg, state, jnp.asarray(keys.astype(np.uint32)),
            jnp.asarray(enabled), qttls if expiry else None)


def _assert_same(got, want):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("b", [1, 64, 512])
@pytest.mark.parametrize("expiry", [False, True], ids=["plain", "expiry"])
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("policy", list(Policy), ids=lambda p: p.name)
def test_guarded_writes_equal_unguarded(policy, batch, expiry, b):
    """State (every lane, the expiry lane and the clock), hits, values,
    evicted keys and their validity, and ``apply_put``'s landing slots are
    bit-identical with and without the guard."""
    cfg, state, qkeys, enabled, ttls = _case(policy, expiry, b, batch)
    qvals = qkeys.astype(jnp.int32) ^ jnp.int32(0x5A5A)
    qkeys, sets, set_keys, hit_raw, way = kway._probe(cfg, state, qkeys)
    admit = jnp.arange(b) % 3 != 1

    args = (cfg, state, qkeys, qvals, sets, hit_raw, way, admit, enabled)
    kw = dict(set_keys=set_keys, ttls=ttls)
    _assert_same(kway.apply_access(*args, **kw),
                 _ORACLE["apply_access"](*args, **kw))
    _assert_same(kway.apply_access(*args, **kw, slot_value=True),
                 _ORACLE["apply_access"](*args, **kw, slot_value=True))

    hit = hit_raw & enabled
    _assert_same(kway.apply_get(cfg, state, sets, hit, way),
                 _ORACLE["apply_get"](cfg, state, sets, hit, way))

    times, _ = kway._batch_times(state, b)
    order = kway._victim_order(cfg, state, sets, set_keys, times)
    args = (cfg, state, qkeys, qvals, sets, hit_raw, way, order, admit,
            enabled)
    _assert_same(kway.apply_put(*args), _ORACLE["apply_put"](*args))


@pytest.mark.parametrize("batch", ["all_hit", "no_hit"])
@pytest.mark.parametrize("policy", [Policy.LRU, Policy.LFU, Policy.FIFO],
                         ids=lambda p: p.name)
def test_sharded_vmap_fallback_matches_unsharded(policy, batch):
    """On one device ``ShardedCache`` vmaps the shard step, so the guard's
    batching rule decides for all shards at once: hits, values, evictions
    and the global keys and values still equal the unsharded cache's, on a
    batch that hits everywhere and one that hits nowhere."""
    gcfg = KWayConfig(num_sets=64, ways=4, policy=policy)
    be = make_backend("jnp", gcfg)
    sc = ShardedCache(ShardedConfig(cache=gcfg, num_shards=4))
    rng = np.random.default_rng(1)
    warm = rng.permutation(1 << 16)[:96].astype(np.uint32)
    single, shard = be.init(), sc.init()

    def step(keys):
        nonlocal single, shard
        vals = keys.astype(np.int32)
        single, h1, v1, ek1, ev1 = be.access(single, jnp.asarray(keys),
                                             jnp.asarray(vals))
        shard, h2, v2, ek2, ev2 = sc.access(shard, keys, vals)
        np.testing.assert_array_equal(np.asarray(h1), h2)
        np.testing.assert_array_equal(np.asarray(v1), v2)
        np.testing.assert_array_equal(np.asarray(ev1), ev2)
        np.testing.assert_array_equal(np.asarray(ek1)[np.asarray(ev1)],
                                      np.asarray(ek2)[np.asarray(ev2)])
        return np.asarray(h1)

    step(warm[:48])
    step(warm[48:])
    # The last batch reaches half the shards, so the shards' predicates
    # differ and the batching rule has to reduce them.
    if batch == "all_hit":
        keys = np.asarray(single.keys).ravel()
        keys = keys[keys != hashing.EMPTY_KEY]
    else:
        keys = rng.permutation(1 << 16).astype(np.uint32) + np.uint32(1 << 20)
    keys = rng.choice(keys[sc.owner_of(keys) < 2], 48)
    hits = step(keys)
    assert hits.all() if batch == "all_hit" else not hits.any()
    gv = sc.global_view(shard)
    np.testing.assert_array_equal(np.asarray(gv.keys), np.asarray(single.keys))
    np.testing.assert_array_equal(np.asarray(gv.vals), np.asarray(single.vals))


@pytest.mark.parametrize("policy,guards", [(Policy.LRU, 2), (Policy.FIFO, 1)],
                         ids=["lru", "fifo"])
def test_sharded_vmap_step_keeps_one_guard_per_phase(policy, guards):
    """The vmapped shard step (CPU lowering) keeps each guard as one loop
    for all shards, and selects over no lane: without the batching rule a
    batched predicate would run the scatters and select whole stacked lanes
    ``[shards, sets * ways]``.  FIFO writes nothing on a hit, so it has the
    insert guard alone."""
    shards, local_sets, ways = 4, 16, 4
    sc = ShardedCache(ShardedConfig(
        cache=KWayConfig(num_sets=shards * local_sets, ways=ways,
                         policy=policy), num_shards=shards))
    state = sc.init()
    keys = np.arange(48, dtype=np.uint32)
    sc.access(state, keys, keys.astype(np.int32))
    (step,) = sc._fns.values()
    text = step.lower(jnp.asarray(keys), jnp.asarray(keys.astype(np.int32)),
                      state, jnp.zeros((shards,), jnp.int32)).as_text()
    assert text.count("stablehlo.while") == guards
    lanes = {f"{shards}x{local_sets * ways}", f"{shards}x{local_sets}x{ways}"}
    selects = [ln for ln in text.splitlines() if "stablehlo.select" in ln]
    assert selects
    for ln in selects:
        assert not lanes & set(re.findall(r"tensor<([\dx]+)x[a-z]", ln)), ln
