"""Device-resident request router — set-owner bucketing as traceable jnp ops.

The paper's parallelism story routes every request to the thread owning its
set before any cache work happens ("hash routing", Fig. 1); "Limited
Associativity Caching in the Data Plane" pushes the same partition-then-route
structure into the forwarding fast path.  This module is that router for the
set-sharded layer (core/sharded.py): pure shape-stable jnp, so routing lives
*inside* jit/vmap/shard_map/lax.scan instead of numpy on the host.

Layout contract (DESIGN.md §9):

  * The owner of a key is the HIGH ``log2(D)`` bits of its *global* set index
    (``owner = gset // (S/D)``); the LOW bits are the shard-local set index,
    so per-shard probing reuses the same hash unchanged.
  * A batch of B requests is bucketed into a **fixed** ``[D, capacity]``
    layout via one stable argsort on the owner id — arrival order is
    preserved inside each bucket, which is what makes the sharded cache
    bit-equal to the unsharded one for timestamp-order-invariant policies.
  * ``capacity`` is static (a ``ShardedConfig`` knob).  The default,
    ``capacity == B``, can never overflow (the degenerate case routes the
    whole batch to one shard).  Smaller capacities trade padding work for an
    **overflow-defer** policy: lanes ranked beyond ``capacity`` in their
    bucket are *not* routed this step — they are reported in
    ``RoutePlan.deferred`` (never silently dropped) and the caller decides
    (``ShardedCache.access`` returns them as unprocessed misses; replay
    counts them as misses and reports the defer total).
  * ``unscatter`` inverts the permutation: per-request results come back in
    the original batch order without a host round trip.

Everything here is shape-static in (B, D, capacity): one XLA compilation per
shape, asserted by the trace counters in core/sharded.py.

Profiler names: ownership, bucketing and its mask run under the
``jax.named_scope`` ``router.route``, the inverse permutation under
``router.unscatter`` (op metadata only; core/sharded.py names the per-shard
step ``shard.access``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoutePlan:
    """Where every request of one batch goes: shard ``owner``, arrival rank
    ``pos`` inside that shard's bucket, and the overflow-``deferred`` mask.
    A pytree of [B] arrays — scan/vmap-safe."""

    owner: jnp.ndarray     # int32 [B]  owning shard (high bits of gset)
    pos: jnp.ndarray       # int32 [B]  arrival rank within the owner bucket
    deferred: jnp.ndarray  # bool  [B]  ranked past capacity: not routed
    enabled: jnp.ndarray   # bool  [B]  the caller's lane mask (pre-defer)

    @property
    def routed(self) -> jnp.ndarray:
        """Lanes that actually land in a bucket this step."""
        return self.enabled & ~self.deferred


def pad_chunks(trace: np.ndarray, batch: int):
    """Chunk a trace for batched replay, padding the trailing
    ``len % batch`` requests into a disabled-lane tail chunk (no request is
    silently dropped).  The single definition shared by the unsharded
    (simulate) and sharded replay paths.  -> (chunks [steps, B] uint32,
    enabled [steps, B] bool), as host arrays.
    """
    trace = np.asarray(trace, np.uint32)
    n = trace.shape[0]
    steps = -(-n // batch)
    padded = np.zeros((steps * batch,), np.uint32)
    padded[:n] = trace
    enabled = np.zeros((steps * batch,), bool)
    enabled[:n] = True
    return padded.reshape(steps, batch), enabled.reshape(steps, batch)


@jax.named_scope("router.route")
def owner_of(keys: jnp.ndarray, num_sets: int, num_shards: int,
             seed: int) -> jnp.ndarray:
    """Owning shard per key: high bits of the global set index. int32 [B]."""
    gset = hashing.set_index(
        jnp.asarray(keys, jnp.uint32), num_sets, seed)
    return gset // jnp.int32(num_sets // num_shards)


@jax.named_scope("router.route")
def route(owner: jnp.ndarray, num_shards: int, capacity: int,
          enabled: Optional[jnp.ndarray] = None) -> RoutePlan:
    """Stable-argsort bucketing of one batch.  Traceable, shape-static.

    ``pos[i]`` is the number of earlier enabled requests owned by the same
    shard — the vectorized equivalent of appending to D per-shard queues in
    arrival order.  Disabled lanes rank last in every bucket (they never
    displace a real request) and are never routed.
    """
    b = owner.shape[0]
    if enabled is None:
        enabled = jnp.ones((b,), jnp.bool_)
    if num_shards == 1:
        # Degenerate routing is the identity: one bucket, arrival order.
        pos = jnp.cumsum(enabled.astype(jnp.int32)) - 1
        pos = jnp.where(enabled, pos, b)
        return RoutePlan(owner=jnp.zeros((b,), jnp.int32), pos=pos,
                         deferred=enabled & (pos >= capacity),
                         enabled=enabled)
    # Disabled lanes sort under a sentinel owner id past every real shard.
    key = jnp.where(enabled, owner, jnp.int32(num_shards))
    perm = jnp.argsort(key, stable=True)       # arrival order kept per shard
    sorted_key = key[perm]
    idx = jnp.arange(b, dtype=jnp.int32)
    new_group = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_key[1:] != sorted_key[:-1]])
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_group, idx, 0))
    pos = jnp.zeros((b,), jnp.int32).at[perm].set(idx - group_start)
    pos = jnp.where(enabled, pos, b)
    return RoutePlan(owner=owner, pos=pos,
                     deferred=enabled & (pos >= capacity), enabled=enabled)


def _dest(plan: RoutePlan, capacity: int, num_shards: int) -> jnp.ndarray:
    """Flat [D*capacity] scatter index per lane; un-routed lanes point one
    past the end and are dropped by the scatter."""
    return jnp.where(plan.routed, plan.owner * capacity + plan.pos,
                     jnp.int32(num_shards * capacity))


@jax.named_scope("router.route")
def bucket(plan: RoutePlan, values: jnp.ndarray, num_shards: int,
           capacity: int, fill) -> jnp.ndarray:
    """Scatter a per-request [B] array into the [D, capacity] bucket layout.
    Padding lanes hold ``fill``."""
    flat = jnp.full((num_shards * capacity,), fill, values.dtype)
    flat = flat.at[_dest(plan, capacity, num_shards)].set(values, mode="drop")
    return flat.reshape(num_shards, capacity)


@jax.named_scope("router.route")
def bucket_mask(plan: RoutePlan, num_shards: int,
                capacity: int) -> jnp.ndarray:
    """The [D, capacity] enabled mask: True exactly where a request landed."""
    flat = jnp.zeros((num_shards * capacity,), jnp.bool_)
    flat = flat.at[_dest(plan, capacity, num_shards)].set(
        plan.routed, mode="drop")
    return flat.reshape(num_shards, capacity)


@jax.named_scope("router.unscatter")
def unscatter(plan: RoutePlan, bucketed: jnp.ndarray, fill) -> jnp.ndarray:
    """Inverse permutation: gather per-request results [B] back into the
    original batch order from the [D, capacity, ...] bucket layout.
    Deferred/disabled lanes read ``fill``."""
    d, capacity = bucketed.shape[:2]
    flat = bucketed.reshape((d * capacity,) + bucketed.shape[2:])
    take = jnp.where(plan.routed, plan.owner * capacity + plan.pos, 0)
    out = flat[take]
    mask = plan.routed.reshape((-1,) + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, jnp.asarray(fill, bucketed.dtype))
