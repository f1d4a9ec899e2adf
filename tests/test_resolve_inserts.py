"""Insert conflict resolution (``kway._resolve_inserts``): the sort-carried
form against the permutation gather/scatter form it replaced, bit for bit,
and a guard that the compiled program resolves with sorts alone."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kway
from repro.core.hashing import EMPTY_KEY
from repro.core.kway import KWayConfig
from repro.core.policies import Policy

CFG = KWayConfig(num_sets=16, ways=8, policy=Policy.LRU)


# ---------------------------------------------------------------------------
# oracle: argsort, then gathers through the permutation and scatters that
# invert it (the form the cache used before the sort-carried one)
# ---------------------------------------------------------------------------

def _oracle_rank(sets, active):
    b = sets.shape[0]
    order_key = jnp.where(active, sets, jnp.int32(0x7FFFFFFF))
    perm = jnp.argsort(order_key, stable=True)
    sorted_sets = order_key[perm]
    new_group = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_sets[1:] != sorted_sets[:-1]])
    idx = jnp.arange(b, dtype=jnp.int32)
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_group, idx, 0))
    rank = jnp.zeros((b,), jnp.int32).at[perm].set(idx - group_start)
    return jnp.where(active, rank, 0)


def _oracle_first(qkeys, active):
    b = qkeys.shape[0]
    order_key = jnp.where(active, qkeys, EMPTY_KEY).astype(jnp.uint32)
    perm = jnp.argsort(order_key, stable=True)
    sorted_keys = order_key[perm]
    first_sorted = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_keys[1:] != sorted_keys[:-1]])
    first = jnp.zeros((b,), jnp.bool_).at[perm].set(first_sorted)
    return first & active


@partial(jax.jit, static_argnums=0)
def _oracle_resolve(cfg, qkeys, sets, eligible, order):
    is_insert = eligible & _oracle_first(qkeys, eligible)
    rank = _oracle_rank(sets, is_insert)
    is_insert &= rank < cfg.ways
    rank_c = jnp.clip(rank, 0, order.shape[1] - 1)
    way_victim = jnp.take_along_axis(order, rank_c[:, None], axis=-1)[:, 0]
    return is_insert, way_victim


_resolve = jax.jit(kway._resolve_inserts, static_argnums=0)


def _batch(pattern, b, m, rng):
    """(qkeys, sets, eligible, order) for one batch.  Equal keys always
    share a set, as the probe's set index guarantees."""
    if pattern == "heavy_dups":
        keys = rng.integers(0, max(1, b // 8), b)
    elif pattern == "all_eligible":
        keys = rng.integers(0, max(1, b // 2), b)
    else:                                   # distinct keys
        keys = rng.permutation(1 << 20)[:b]
    keys = keys.astype(np.uint32) * np.uint32(2654435761)
    if pattern == "overfull":               # every key into two sets
        sets = (keys >> np.uint32(7)) % np.uint32(2)
    else:
        sets = (keys >> np.uint32(7)) % np.uint32(CFG.num_sets)
    eligible = {
        "all_eligible": np.ones(b, bool),
        "overfull": np.ones(b, bool),
        "none_eligible": np.zeros(b, bool),
    }.get(pattern, rng.random(b) < 0.6)
    if m == CFG.ways:
        order = np.argsort(rng.random((b, m)), axis=-1)
    else:                                   # sampled: ways with replacement
        order = rng.integers(0, CFG.ways, (b, m))
    return (jnp.asarray(keys), jnp.asarray(sets.astype(np.int32)),
            jnp.asarray(eligible), jnp.asarray(order.astype(np.int32)))


@pytest.mark.parametrize("m", [CFG.ways, 3], ids=["order_k", "order_m3"])
@pytest.mark.parametrize("pattern", [
    "no_dups", "heavy_dups", "all_eligible", "none_eligible", "overfull"])
@pytest.mark.parametrize("b", [1, 7, 64, 4096])
def test_resolve_matches_gather_scatter_oracle(b, pattern, m):
    rng = np.random.default_rng([b, m, len(pattern)])
    for _ in range(4):
        args = _batch(pattern, b, m, rng)
        want_ins, want_way = _oracle_resolve(CFG, *args)
        got_ins, got_way = _resolve(CFG, *args)
        np.testing.assert_array_equal(np.asarray(got_ins), np.asarray(want_ins))
        np.testing.assert_array_equal(np.asarray(got_way), np.asarray(want_way))
        assert got_way.dtype == want_way.dtype
    if pattern == "overfull" and b >= 64:
        # the cap bites: exactly k admits in each of the two sets
        assert int(np.asarray(got_ins).sum()) == 2 * CFG.ways


def test_resolve_compiles_to_sorts_without_gathers_or_scatters():
    """At getput's geometry (8192 sets x 8 ways, LRU, B = 4096) no gather
    or scatter of the compiled access apply is booked to ``kway.resolve``,
    and its sorts are."""
    cfg = KWayConfig(num_sets=8192, ways=8, policy=Policy.LRU)
    b = 4096
    state = jax.eval_shape(lambda: kway.make_cache(cfg))

    def spec(dtype, shape=(b,)):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = kway.apply_access.lower(
        cfg, state, spec(jnp.uint32), spec(jnp.int32), spec(jnp.int32),
        spec(jnp.bool_), spec(jnp.int32),
        set_keys=spec(jnp.uint32, (b, cfg.ways))).compile().as_text()

    def resolve_ops(opcode):
        return [ln for ln in text.splitlines()
                if re.search(rf"\s{opcode}\(", ln) and "kway.resolve" in ln]

    assert resolve_ops("scatter") == []
    assert resolve_ops("gather") == []
    assert len(resolve_ops("sort")) == 3
