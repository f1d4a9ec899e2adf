"""Trace replay -> hit ratio, the hit-ratio study engine (paper §5.2).

A front end over ``CacheBackend.replay``, which owns the chunked replay
loop.  ``replay_batched`` cuts a trace into B-request chunks (the tail
chunk padded with disabled lanes, so the ratio covers every request) and
makes one ``replay`` call on the backend ``SimConfig.backend`` names;
``replay`` is the same at B = 1, the exact sequential semantics of the
paper's single-threaded hit-ratio measurements.

Where each choice is made:

  * ``jnp``: one jitted scan over the chunks
    (``CacheBackend.replay``), with TinyLFU admission, per-request TTLs or
    the unfused ``two_phase`` oracle as options of that one scan.
  * ``pallas``: ``PallasBackend.replay`` picks from the VMEM fit — the
    trace-resident megakernel, or the same scan through the probe kernels
    (and the scan for ``two_phase``).
  * ``ref``: ``RefBackend.replay``, the host loop of the sequential oracle.
  * ``hierarchy``: the L1-over-L2 replay of the backend (DESIGN.md §14).
  * ``shards > 1``: the set-sharded layer (core/sharded.py) instead.

The backend module's ``_check_replay`` refuses the options that do not
compose, in one place; ``replay_batched`` calls it before it picks the
sharded path, so every path refuses with the same message.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import admission, router
from repro.core.backend import _check_replay, make_backend
from repro.core.kway import KWayConfig


@dataclasses.dataclass(frozen=True)
class SimConfig:
    cache: KWayConfig
    tinylfu: Optional[admission.TinyLFUConfig] = None  # None = admit always
    backend: str = "jnp"
    # True: replay through the unfused get-then-put composition
    # (backend.access_two_phase) instead of the fused single-probe access —
    # the differential-oracle knob for the fused path.
    two_phase: bool = False


@lru_cache(maxsize=None)
def _cached_backend(name: str, cache: KWayConfig):
    """Backend instances memoized per config so their per-instance jit
    caches (CacheBackend._replay_fns) survive across replay calls —
    backends are functional, so sharing instances is safe."""
    return make_backend(name, cache)


def replay(sim: SimConfig, trace: np.ndarray) -> float:
    """Exact sequential replay (B = 1) -> hit ratio."""
    return replay_batched(sim, trace, batch=1)


def _pad_ttl_chunks(ttls: np.ndarray, batch: int) -> np.ndarray:
    """Chunk a per-request TTL array [n] -> int32 [steps, B] with the same
    steps/batch geometry as ``router.pad_chunks`` (padding lanes carry
    ttl 0 == never expires; they are disabled anyway)."""
    ttls = np.asarray(ttls, np.int32)
    n = ttls.shape[0]
    steps = -(-n // batch)
    padded = np.zeros((steps * batch,), np.int32)
    padded[:n] = ttls
    return padded.reshape(steps, batch)


def replay_batched(
    sim: SimConfig, trace: np.ndarray, batch: int = 64, shards: int = 1,
    hierarchy=None, ttls=None,
) -> float:
    """Batched replay -> hit ratio over the WHOLE trace (the tail chunk is
    padded with disabled lanes on every path).

    ``shards`` > 1 replays through the set-sharded layer
    (``ShardedCache.replay``); only the sequential-Python ``ref`` oracle
    cannot be sharded.

    ``hierarchy`` (a ``HierarchyConfig`` with ``l1_sets > 0``) selects the
    L1-over-L2 replay mode (DESIGN.md §14); ``l1_sets == 0`` is the flat
    path unchanged.

    ``ttls`` (int32 [n], optional, aligned with ``trace``) gives each
    request a time-to-live on the logical replay clock (DESIGN.md §15):
    a request that misses inserts with deadline ``clock + 2B + ttl``
    (``ttl <= 0`` = never expires), and an entry whose deadline has passed
    is never served as a hit on any path."""
    trace = np.asarray(trace, np.uint32)
    n = trace.shape[0]
    if ttls is not None:
        ttls = np.asarray(ttls, np.int32)
        if ttls.shape[0] != n:
            raise ValueError(
                f"ttls length {ttls.shape[0]} != trace length {n}")
    hierarchy = _check_replay(sim.tinylfu, hierarchy, ttls, sim.two_phase)
    if shards > 1:
        if sim.backend == "ref":
            raise ValueError(
                "the ref backend is sequential host Python and cannot be "
                "sharded; use backend='jnp' or 'pallas' with shards > 1")
        from repro.core.sharded import ShardedCache, ShardedConfig

        sc = ShardedCache(ShardedConfig(
            cache=sim.cache, num_shards=shards, backend=sim.backend))
        hits, _, _ = sc.replay(trace, batch, tinylfu=sim.tinylfu,
                               two_phase=sim.two_phase,
                               resident=hierarchy is not None,
                               hierarchy=hierarchy, ttls=ttls)
        return hits / n
    be = _cached_backend(sim.backend, sim.cache)
    chunks, enabled = router.pad_chunks(trace, batch)
    hits, _, _, _ = be.replay(
        be.init(ttl=ttls is not None), chunks, enabled, tinylfu=sim.tinylfu,
        hierarchy=hierarchy,
        ttls=None if ttls is None else _pad_ttl_chunks(ttls, batch),
        two_phase=sim.two_phase)
    return float(jnp.sum(hits)) / n
