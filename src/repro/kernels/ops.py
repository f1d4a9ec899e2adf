"""Jit'd public wrappers around the Pallas kernels.

These adapt the framework's pytree state to the kernels' padded VMEM layouts
and leave interpret mode to the kernels' default
(``repro.kernels.interpret()``: compiled on a TPU, the Pallas interpreter
elsewhere).  The rest of the framework calls only these entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.kway import KWayConfig, KWayState
from repro.kernels import interpret
from repro.kernels import kway_probe as _kp
from repro.kernels import paged_attention as _pa
from repro.kernels import ref as _ref


def query_tile(b: int) -> int:
    """Queries per probe-kernel grid step for a batch of ``b``: one full
    128-lane tile for large batches, the batch rounded up to the 8-row
    sublane tile for small ones (so a decode tick of 8 lanes is not padded
    to 128 dummy probes)."""
    return min(_kp.LANES, -(-b // 8) * 8)


def _pad_ways(arr: jnp.ndarray, lanes: int = _kp.LANES,
              fill: int = -1) -> jnp.ndarray:
    s, k = arr.shape
    if k == lanes:
        return arr
    pad = jnp.full((s, lanes - k), fill, arr.dtype)
    return jnp.concatenate([arr, pad], axis=1)


def _probe_impl(cfg, state, qkeys, use_kernel: bool, full_order: bool,
                need_victims: bool = True):
    """Shared probe core: sanitize + route + pad to the query tile.

    Padding with dummy probes keeps the kernel on every batch size (probing
    is read-only, so padding lanes are harmless); outputs are sliced back
    to B.  Returns (qkeys_sanitized, sets, outs) with outs = the kernel's
    output tuple, already sliced.
    """
    qkeys = hashing.sanitize_keys(qkeys)
    sets = hashing.set_index(qkeys, cfg.num_sets, cfg.seed)
    b = qkeys.shape[0]
    times = state.clock + jnp.arange(b, dtype=jnp.int32)

    keys_i = _pad_ways(state.keys.astype(jnp.int32))
    fpr = _pad_ways(state.fprint.astype(jnp.int32), fill=0)
    ma = _pad_ways(state.meta_a)
    mb = _pad_ways(state.meta_b)
    qk_i = qkeys.astype(jnp.int32)

    qt = query_tile(b)
    if use_kernel:
        pad = (-b) % qt
        zpad = jnp.zeros((pad,), jnp.int32)
        outs = _kp.kway_probe(
            keys_i, fpr, ma, mb,
            jnp.concatenate([sets, zpad]),
            jnp.concatenate([qk_i, zpad]),
            jnp.concatenate([times, zpad]),
            policy=int(cfg.policy), ways=cfg.ways, qt=qt,
            full_order=full_order,
            need_victims=need_victims,
        )
    else:
        outs = _ref.kway_probe_ref(
            keys_i, fpr, ma, mb, sets, qk_i, times,
            policy=int(cfg.policy), ways=cfg.ways, full_order=full_order,
            need_victims=need_victims,
        )
    return qkeys, sets, tuple(o[:b] for o in outs)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("use_kernel",))
def probe(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    *,
    use_kernel: bool = True,
):
    """Kernel-accelerated probe of the K-way cache.

    Returns (qkeys_sanitized uint32[B], sets int32[B], hit bool[B],
    way int32[B], victim_way int32[B], victim_key uint32[B]) — the decisions
    the caller's scatter applies.  ``use_kernel=False`` selects the pure-jnp
    oracle.
    """
    qkeys, sets, (hit, way, vway, vkey) = _probe_impl(
        cfg, state, qkeys, use_kernel, full_order=False)
    return (qkeys, sets, hit.astype(jnp.bool_), way, vway,
            vkey.astype(jnp.uint32))


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("use_kernel",))
def probe_hits(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    *,
    use_kernel: bool = True,
):
    """Read-path probe: hit decisions only, no victim selection.

    The pure-get path never consumes victim scores, so this variant skips
    the score computation and the victim-extraction rounds entirely
    (``need_victims=False`` in the kernel).  Returns (qkeys_sanitized
    uint32[B], sets int32[B], hit bool[B], way int32[B]).
    """
    qkeys, sets, (hit, way) = _probe_impl(
        cfg, state, qkeys, use_kernel, full_order=False, need_victims=False)
    return qkeys, sets, hit.astype(jnp.bool_), way


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("use_kernel",))
def fused_probe(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    enabled: jnp.ndarray = None,
    *,
    use_kernel: bool = True,
):
    """Single-launch fused probe for ``access`` (get; on miss, put).

    One kernel launch serves both phases: hit decisions come from the probe,
    and the full victim order is scored inside the kernel on a hit-updated
    VMEM copy of ``meta_a`` at the put-phase timestamps (t+B+i) — exactly
    what ``probe`` followed by ``probe_orders`` on the post-get state would
    produce, at half the launches and HBM traffic.

    Returns (qkeys_sanitized uint32[B], sets int32[B], hit bool[B] (raw,
    unmasked by ``enabled``), way int32[B], order int32[B, ways]) — what
    ``core/kway.apply_access`` consumes.
    """
    qkeys = hashing.sanitize_keys(qkeys)
    sets = hashing.set_index(qkeys, cfg.num_sets, cfg.seed)
    b = qkeys.shape[0]
    times_get = state.clock + jnp.arange(b, dtype=jnp.int32)
    times_put = times_get + jnp.int32(b)
    en = (jnp.ones((b,), jnp.int32) if enabled is None
          else enabled.astype(jnp.int32))

    keys_i = _pad_ways(state.keys.astype(jnp.int32))
    fpr = _pad_ways(state.fprint.astype(jnp.int32), fill=0)
    ma = _pad_ways(state.meta_a)
    mb = _pad_ways(state.meta_b)
    qk_i = qkeys.astype(jnp.int32)

    qt = query_tile(b)
    if use_kernel:
        pad = (-b) % qt
        zpad = jnp.zeros((pad,), jnp.int32)
        # padding lanes carry en=0: they must not apply hit updates
        outs = _kp.kway_fused_probe(
            keys_i, fpr, ma, mb,
            jnp.concatenate([sets, zpad]),
            jnp.concatenate([qk_i, zpad]),
            jnp.concatenate([times_get, zpad]),
            jnp.concatenate([times_put, zpad]),
            jnp.concatenate([en, zpad]),
            policy=int(cfg.policy), ways=cfg.ways, qt=qt,
        )
    else:
        outs = _ref.kway_fused_probe_ref(
            keys_i, fpr, ma, mb, sets, qk_i, times_get, times_put, en,
            policy=int(cfg.policy), ways=cfg.ways,
        )
    hit, way, order = (o[:b] for o in outs)
    return (qkeys, sets, hit.astype(jnp.bool_), way, order[:, : cfg.ways])


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("use_kernel",))
def probe_orders(
    cfg: KWayConfig,
    state: KWayState,
    qkeys: jnp.ndarray,
    *,
    use_kernel: bool = True,
):
    """Kernel probe + full victim order — the PallasBackend's write phase.

    Returns (qkeys_sanitized uint32[B], sets int32[B], hit bool[B],
    way int32[B], order int32[B, ways]) where ``order`` lists each query's
    set's ways worst-victim-first, exactly what core/kway.apply_put consumes.
    Requires cfg.ways <= LANES and cfg.sample == 0 (enforced by the backend).
    """
    qkeys, sets, (hit, way, _, _, order) = _probe_impl(
        cfg, state, qkeys, use_kernel, full_order=True)
    return qkeys, sets, hit.astype(jnp.bool_), way, order[:, : cfg.ways]


def replay_resident(cfg: KWayConfig, state: KWayState, chunks, enabled,
                    tinylfu=None, sketch=None, ttls=None):
    """Whole-trace replay in ONE pallas launch (kernels/replay.py).

    ``chunks`` uint32 [steps, B] / ``enabled`` bool [steps, B] — the
    ``router.pad_chunks`` layout.  The cache state lanes stay VMEM-resident
    for the entire trace; the per-chunk transitions are bit-identical to
    scanning the chunks through the fused ``access`` (with the TinyLFU
    record → peek → admit phases of the batched replay when ``tinylfu``).
    ``ttls`` (int32 [steps, B], optional) turns on the expiry lane
    (DESIGN.md §15); requires ``state.expiry`` and excludes TinyLFU.

    Returns (hits int32 [steps], evs int32 [steps], state', sketch'|None).
    """
    from repro.kernels import replay as _rp

    hits, evs, lanes, sketch_out = _rp.replay_resident(
        state.keys, state.fprint, state.vals, state.meta_a, state.meta_b,
        state.clock,
        jnp.asarray(chunks, jnp.uint32), jnp.asarray(enabled, jnp.bool_),
        policy=int(cfg.policy), ways=cfg.ways, num_sets=cfg.num_sets,
        seed=cfg.seed, tinylfu=tinylfu, sketch=sketch,
        expiry=state.expiry, ttls=ttls,
    )
    keys, fpr, vals, ma, mb, clock = lanes[:6]
    state_out = KWayState(keys=keys, fprint=fpr, vals=vals, meta_a=ma,
                          meta_b=mb, clock=clock,
                          expiry=lanes[6] if len(lanes) > 6 else None)
    return hits, evs, state_out, sketch_out


def replay_hierarchical(cfg: KWayConfig, hier, state, chunks, enabled,
                        ttls=None):
    """Whole-trace replay through the L1-over-L2 hierarchy in ONE pallas
    launch (kernels/replay.py, hierarchical megakernel).

    ``state`` is a :class:`repro.core.hierarchy.HierState`; ``chunks`` /
    ``enabled`` the ``router.pad_chunks`` layout.  Bit-identical to the
    jnp twin ``core/hierarchy.replay_l1_over_l2`` (the differential
    oracle) — same per-chunk hit/eviction counts and final tier states.
    ``ttls`` (int32 [steps, B], optional) turns on the per-lane expiry
    path (DESIGN.md §15): rows are lazily scrubbed at the batch-exit
    horizon before probing, so an expired key is never a hit on either
    tier; requires tier states built with expiry lanes.

    Returns (hits int32 [steps], evs int32 [steps], HierState', None).
    """
    from repro.core.hierarchy import HierState
    from repro.core.kway import KWayState as _KWS
    from repro.kernels import replay as _rp

    l1, l2 = state.l1, state.l2
    hits, evs, l1_f, l2_f, clock_f = _rp.replay_hierarchical(
        l1.keys, l1.fprint, l1.vals, l1.meta_a, l1.meta_b,
        l2.keys, l2.fprint, l2.vals, l2.meta_a, l2.meta_b,
        l2.clock,
        jnp.asarray(chunks, jnp.uint32), jnp.asarray(enabled, jnp.bool_),
        policy=int(cfg.policy), l1_ways=hier.l1_ways, l2_ways=cfg.ways,
        l1_sets=hier.l1_sets, l2_sets=cfg.num_sets, seed=cfg.seed,
        promote=hier.promote, demote=hier.demote,
        l1_exp=l1.expiry, l2_exp=l2.expiry, ttls=ttls,
    )

    def unpack(lanes):
        k, f, v, a, b = lanes[:5]
        return _KWS(keys=k.astype(jnp.uint32), fprint=f.astype(jnp.uint32),
                    vals=v, meta_a=a, meta_b=b, clock=clock_f,
                    expiry=lanes[5] if len(lanes) > 5 else None)

    return hits, evs, HierState(l1=unpack(l1_f), l2=unpack(l2_f)), None


def attend_paged(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    scale: float | None = None,
    softcap: float = 0.0,
    use_kernel: bool | None = None,
) -> jnp.ndarray:
    """Paged GQA decode attention (see kernels/paged_attention.py).

    ``use_kernel=None`` picks per accelerator: the Pallas kernel on TPU, the
    vectorized jnp reference elsewhere.  Off-TPU the kernel only runs in
    interpret mode — a per-grid-point Python loop that is a correctness
    oracle, not an execution path (a [32, 4, 32] decode grid is ~4k
    interpreted kernel evals per layer); the reference is a single fused XLA
    computation there.  Pass an explicit bool to force either path.
    """
    if use_kernel is None:
        use_kernel = not interpret()
    if use_kernel:
        return _pa.paged_attention(
            q, k_pages, v_pages, page_table, seq_lens,
            scale=scale, softcap=softcap,
        )
    return _ref.paged_attention_ref(
        q, k_pages, v_pages, page_table, seq_lens, scale=scale, softcap=softcap
    )
