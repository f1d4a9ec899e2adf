"""Core library: the paper's K-way set-associative cache and its ecosystem.

Public API:
    KWayConfig, KWayState, make_cache, get, put, access, peek_victims
    fully_associative  — the paper's baseline as the S=1 corner case
    Policy             — LRU / LFU / FIFO / RANDOM / HYPERBOLIC
    TinyLFU admission  — admission.{TinyLFUConfig, make_sketch, record, admit}
    CacheBackend layer — backend.{make_backend, available_backends}
                         ("jnp" | "pallas" | "ref", one contract — DESIGN.md §3)
    Set sharding       — sharded.{ShardedConfig, ShardedCache} (DESIGN.md §5)
    Request routing    — router.{route, bucket, unscatter}: the device-
                         resident owner router behind sharding (DESIGN.md §9)
    CacheBackend.replay — the one chunked replay loop on one device; the
                         pallas backend picks the megakernel from the VMEM
                         fit, the ref oracle loops on the host
    simulate.replay    — hit-ratio front end over CacheBackend.replay
    traces.generate    — synthetic workload families
"""
from repro.core.backend import (  # noqa: F401
    CacheBackend,
    available_backends,
    make_backend,
)
from repro.core.kway import (  # noqa: F401
    KWayConfig,
    KWayState,
    access,
    fully_associative,
    get,
    make_cache,
    peek_victims,
    put,
)
from repro.core.policies import Policy  # noqa: F401
