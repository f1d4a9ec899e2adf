"""Pallas TPU kernel: batched K-way set probe + policy victim selection.

This is the paper's hot loop — "scan the k ways of one set, find the key or
the policy victim" (Algorithms 2/3/5/6) — as a VMEM-tiled TPU kernel.

TPU adaptation (DESIGN.md §2):
  * The cache's SoA lanes (keys / fprint / meta_a / meta_b / vals) are
    VMEM-resident:
    a hot cache of S×k ≤ 64Ki entries is ≤ 1 MiB per lane — the software
    analogue of the paper's "short continuous region of memory" argument,
    transplanted to the HBM→VMEM hierarchy.  Each lane is copied whole into
    VMEM once per launch and every grid step indexes it; the pallas backend
    refuses a cache whose lanes exceed ``VMEM_LIMIT``.
  * Each grid step processes ``qt`` queries in a ``fori_loop``.  Per query,
    the set row is fetched with a dynamic slice (``pl.ds``) — the TPU
    equivalent of the paper's pointer-free set scan; ways are padded to the
    128-lane register width so the k-wide compare/reduce is a single VPU op.
  * Set indices, keys and times arrive as SMEM blocks, so each is a scalar
    load that can address VMEM; per-query results go back through SMEM.

The kernel returns probe *decisions* (hit, way, victim way, victim key);
applying them is a single XLA scatter done by the caller (``ops.py``) — a
clean read-kernel / write-scatter split that keeps the kernel free of
scatter hazards (the paper's CAS loop lives in the caller's deterministic
conflict resolution, see core/kway.py).

Expiry (DESIGN.md §15) never reaches this kernel: TTL-aware replay scrubs
expired lanes to EMPTY_KEY *before* probing (``kway.scrub_expired``), so by
the time the probe runs an expired entry is an ordinary empty lane — it can
neither hit nor outrank an empty-way victim.  The probe therefore needs no
expiry lane and no functional change for TTLs.

Validated in ``interpret=True`` mode against ``ref.py`` (pure jnp oracle)
over shape/dtype/policy sweeps in tests/test_kernels.py, and compiled for a
TPU v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.policies import Policy

NEG_INF = -3.0e38  # python literal: jnp module-level constants would be
POS_INF = 3.0e38   # captured by the kernel trace and rejected by pallas_call
LANES = 128  # TPU vector register lane width


def _hash_u32(x, seed: int):
    """core/hashing.hash_u32 (seeded premix + fmix32), inlined with literal
    constants: a pallas_call body cannot close over hashing's module-level
    jnp constants (rejected at trace time), but pure-function reuse is fine —
    this is the ONE kernel-side copy, shared by the victim-score RANDOM
    branch, the fingerprint pre-filter, and the replay megakernel's TinyLFU
    sketch (kernels/replay.py).  The kernel-vs-oracle sweeps in
    tests/test_kernels.py call hashing directly, so drift here fails loudly.
    """
    x = x.astype(jnp.uint32)
    x = (x + jnp.uint32(seed) * jnp.uint32(0x9E3779B1)) * jnp.uint32(0x85EBCA77)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _scores_for_policy(policy: int, keys, meta_a, meta_b, now):
    """Victim scores, lower == evict first.  Bit-identical to
    core/policies.victim_scores (the backend-equivalence suite relies on it),
    written with only Pallas-TPU-lowerable ops (no gather, no PRNG)."""
    a = meta_a.astype(jnp.float32)
    if policy == Policy.RANDOM:
        h = _hash_u32(keys.astype(jnp.uint32) ^ now.astype(jnp.uint32),
                      0xBADA)
        # uint32 -> float32 as hi * 2^16 + lo: both halves and the product
        # are exact, so the one rounding of the sum equals the direct
        # conversion's (which Mosaic cannot lower)
        hi = (h >> 16).astype(jnp.int32).astype(jnp.float32)
        lo = (h & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
        return hi * 65536.0 + lo
    if policy == Policy.HYPERBOLIC:
        age = (now - meta_b).astype(jnp.float32) + 1.0
        return a / age
    return a  # LRU / LFU / FIFO share "argmin meta_a"


def _full_order_row(scores, lane, ways):
    """Full victim order, worst-first: `ways` rounds of masked min-extraction
    (the paper's O(k) scan, k unrolled VPU reduces).  Ties break toward the
    lowest lane — identical to the stable argsort in core/kway._victim_order.
    Returns (ord_row [1, LANES], vway scalar)."""
    work = scores
    ord_row = jnp.full((1, LANES), LANES, jnp.int32)
    vway = None
    for r in range(ways):
        m = jnp.min(work)
        w = jnp.min(jnp.where(work == m, lane, LANES))
        ord_row = jnp.where(lane == r, w, ord_row)
        work = jnp.where(lane == w, POS_INF, work)
        if r == 0:
            vway = w
    return ord_row, vway


def _fingerprint_i32(key_u32):
    """core/hashing.fingerprint as int32 (the kernels' bit-cast lane
    dtype)."""
    return (_hash_u32(key_u32, 0xF19E) & jnp.uint32(0xFFFF)).astype(jnp.int32)


# query inputs ride in SMEM, one [F, qt] block per tile (rows below); the
# per-query scalar outputs go back the same way, one [4, qt] block per tile.
# Scalars live in SMEM: Mosaic cannot store a scalar into VMEM, and a rank-1
# VMEM block must be a multiple of 128 long.
_Q_SET, _Q_KEY, _Q_TIME = 0, 1, 2          # kway_probe query rows
_Q_TPUT, _Q_EN = 3, 4                      # + kway_fused_probe's extra rows
_O_HIT, _O_WAY, _O_VWAY, _O_VKEY = 0, 1, 2, 3


def _query_tiles(rows, qt: int):
    """Stack the int32 [B] query rows into the [B/qt, F, qt] SMEM tiles."""
    q = jnp.stack([r.astype(jnp.int32) for r in rows])          # [F, B]
    return q.reshape(len(rows), -1, qt).transpose(1, 0, 2)


def _untile(out):
    """[B/qt, F, qt] per-tile outputs -> F rows of int32 [B]."""
    nt, f, qt = out.shape
    return tuple(out.transpose(1, 0, 2).reshape(f, nt * qt))


def _probe_kernel(
    q_ref,               # SMEM int32 [3, qt]   (set, key, time) per query
    keys_ref,            # VMEM int32 [S, kp]   stored keys (bit-cast uint32)
    fprint_ref,          # VMEM int32 [S, kp]   16-bit fingerprints
    meta_a_ref,          # VMEM int32 [S, kp]
    meta_b_ref,          # VMEM int32 [S, kp]
    out_ref,             # SMEM int32 [4, qt]   (hit, way, vway, vkey)
    *rest,               # (vorder_ref [qt, LANES],) when full_order
    policy: int,
    ways: int,
    qt: int,
    empty_key: int,
    need_victims: bool,
):
    vorder_ref = rest[0] if rest else None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    valid_way = lane < ways

    def body(i, _):
        s = q_ref[_Q_SET, i]
        row_keys = keys_ref[pl.ds(s, 1), :]          # [1, kp]
        row_fpr = fprint_ref[pl.ds(s, 1), :]
        qk = q_ref[_Q_KEY, i]

        occupied = (row_keys != empty_key) & valid_way
        # KW-WFSC Algorithm 5: the 16-bit fingerprint pre-filters the scan;
        # a fingerprint match is confirmed on the full key, so the result is
        # bit-identical to the plain full-key compare.
        eq = (row_fpr == _fingerprint_i32(qk)) & (row_keys == qk) & occupied
        hit = jnp.any(eq)
        # first matching way (stable argmax over the 128-lane mask)
        way = jnp.min(jnp.where(eq, lane, LANES))

        out_ref[_O_HIT, i] = hit.astype(jnp.int32)
        out_ref[_O_WAY, i] = jnp.where(hit, way, 0)

        if not need_victims:
            # Pure-get probe: skip the victim-selection rounds entirely —
            # the read path never consumes them.
            return 0

        row_a = meta_a_ref[pl.ds(s, 1), :]
        row_b = meta_b_ref[pl.ds(s, 1), :]
        now = q_ref[_Q_TIME, i]
        scores = _scores_for_policy(policy, row_keys, row_a, row_b, now)
        scores = jnp.where(occupied, scores, NEG_INF)  # empty ways first
        scores = jnp.where(valid_way, scores, POS_INF)  # padding ways last
        if vorder_ref is None:
            vscore = jnp.min(scores)
            vway = jnp.min(jnp.where(scores == vscore, lane, LANES))
        else:
            ord_row, vway = _full_order_row(scores, lane, ways)
            vorder_ref[pl.ds(i, 1), :] = ord_row

        out_ref[_O_VWAY, i] = vway
        out_ref[_O_VKEY, i] = jnp.sum(
            jnp.where(lane == vway, row_keys, 0).astype(jnp.int32)
        )
        return 0

    jax.lax.fori_loop(0, qt, body, 0)


def probe_vmem_bytes(num_sets: int) -> int:
    """VMEM the probe kernels hold for a cache of ``num_sets`` sets: four
    whole state lanes at the 128-lane width plus the fused kernel's
    hit-updated ``meta_a`` copy, and 1 MiB for the double-buffered query
    and victim-order tiles and the compiler's own scratch."""
    return 5 * num_sets * LANES * 4 + (1 << 20)


#: scoped-VMEM limit the cache kernels (probe and replay megakernels) compile
#: under; the pallas backend refuses a cache whose ``probe_vmem_bytes``
#: exceed it (core/backend.py), and its resident budget stays below it
VMEM_LIMIT = 32 << 20


def _whole_vmem():
    # whole array, copied into VMEM once (no double buffering) — every grid
    # step indexes it with dynamic set rows
    return pl.BlockSpec(memory_space=pltpu.VMEM)


@functools.partial(
    jax.jit, static_argnames=("policy", "ways", "qt", "interpret",
                              "full_order", "need_victims")
)
def kway_probe(
    keys: jnp.ndarray,     # int32 [S, kp] (ways padded to LANES multiple.. or any kp>=ways)
    fprint: jnp.ndarray,   # int32 [S, kp] 16-bit fingerprints of the keys
    meta_a: jnp.ndarray,   # int32 [S, kp]
    meta_b: jnp.ndarray,   # int32 [S, kp]
    sets: jnp.ndarray,     # int32 [B]
    qkeys: jnp.ndarray,    # int32 [B]
    times: jnp.ndarray,    # int32 [B]
    *,
    policy: int,
    ways: int,
    qt: int = 8,
    interpret: bool | None = None,   # None: repro.kernels.interpret()
    full_order: bool = False,
    need_victims: bool = True,
):
    """Run the probe kernel.  B must be a multiple of qt, and qt a multiple
    of 8 (the victim-order tile is [qt, LANES]); kp (padded ways) must equal
    LANES (one VREG row per set).

    With ``full_order=True`` a fifth output is returned: vorder int32
    [B, LANES], the per-query victim order worst-first (entries past ``ways``
    hold the LANES sentinel) — what the batched conflict resolution in
    core/kway.apply_put consumes for rank>0 same-set collisions.

    With ``need_victims=False`` (the pure-get read path) the victim-selection
    rounds are skipped entirely and only (hit, way) are returned.
    """
    s, kp = keys.shape
    b = sets.shape[0]
    assert kp == LANES, f"pad ways to {LANES} lanes (got {kp})"
    assert b % qt == 0 and qt % 8 == 0
    if interpret is None:
        interpret = kernels.interpret()
    assert need_victims or not full_order, \
        "full_order requires need_victims=True"
    nt = b // qt

    kernel = functools.partial(
        _probe_kernel,
        policy=policy,
        ways=ways,
        qt=qt,
        empty_key=-1,  # EMPTY_KEY 0xFFFFFFFF viewed as int32
        need_victims=need_victims,
    )
    tile = lambda f: pl.BlockSpec((None, f, qt), lambda i: (i, 0, 0),  # noqa: E731
                                  memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((nt, 4, qt), jnp.int32)]
    out_specs = [tile(4)]
    if full_order:
        out_shape.append(jax.ShapeDtypeStruct((b, LANES), jnp.int32))
        out_specs.append(pl.BlockSpec((qt, LANES), lambda i: (i, 0)))
    outs = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[tile(3)] + [_whole_vmem()] * 4,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="kway_probe",
    )(_query_tiles((sets, qkeys, times), qt), keys, fprint, meta_a, meta_b)
    scal = _untile(outs[0])
    res = scal if need_victims else scal[:2]
    return res + tuple(outs[1:])


# ---------------------------------------------------------------------------
# fused access kernel: both phases of `access` in ONE launch
# ---------------------------------------------------------------------------

def _fused_kernel(
    q_ref,               # SMEM int32 [5, qt]  (set, key, t_get, t_put, en)
    keys_ref,            # VMEM int32 [S, kp]
    fprint_ref,          # VMEM int32 [S, kp]
    meta_a_ref,          # VMEM int32 [S, kp]
    meta_b_ref,          # VMEM int32 [S, kp]
    out_ref,             # SMEM int32 [4, qt]  (hit, way, -, -)
    vorder_ref,          # VMEM int32 [qt, LANES]
    scratch_a,           # VMEM int32 [S, kp]  hit-updated meta_a
    *,
    policy: int,
    ways: int,
    qt: int,
    empty_key: int,
):
    """Two grid phases over the same query tiles (grid = (2, B/qt)):

      phase 0 — probe every query and apply its hit-phase ``on_hit``
        metadata transition to a VMEM scratch copy of ``meta_a`` (queries
        run in batch order, so colliding hits accumulate exactly like the
        scatter-add/-max in core/kway.apply_access);
      phase 1 — re-derive (hit, way) from the untouched key lanes and emit
        the full victim order scored on the *post-hit* scratch metadata at
        the put-phase timestamps — what the second launch of the two-phase
        path would compute, without re-reading the cache from HBM.
    """
    phase = pl.program_id(0)
    tile = pl.program_id(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    valid_way = lane < ways

    @pl.when(jnp.logical_and(phase == 0, tile == 0))
    def _init_scratch():
        scratch_a[...] = meta_a_ref[...]

    def probe(i):
        s = q_ref[_Q_SET, i]
        row_keys = keys_ref[pl.ds(s, 1), :]          # [1, kp]
        row_fpr = fprint_ref[pl.ds(s, 1), :]
        qk = q_ref[_Q_KEY, i]
        occupied = (row_keys != empty_key) & valid_way
        # fingerprint pre-filter + full-key confirm (see _probe_kernel)
        eq = (row_fpr == _fingerprint_i32(qk)) & (row_keys == qk) & occupied
        hit = jnp.any(eq)
        way = jnp.min(jnp.where(eq, lane, LANES))    # LANES when no hit
        return s, row_keys, occupied, hit, way

    if policy not in (Policy.FIFO, Policy.RANDOM):  # on_hit is identity
        @pl.when(phase == 0)
        def _hit_phase():
            def body(i, _):
                s, _, _, hit, way = probe(i)
                do = jnp.logical_and(hit, q_ref[_Q_EN, i] != 0)
                row_a = scratch_a[pl.ds(s, 1), :]
                upd = lane == way            # all-false when way == LANES
                if policy == Policy.LRU:
                    new_a = jnp.where(upd, q_ref[_Q_TIME, i], row_a)
                else:                        # LFU / HYPERBOLIC: count += 1
                    new_a = jnp.where(upd, row_a + 1, row_a)
                scratch_a[pl.ds(s, 1), :] = jnp.where(do, new_a, row_a)
                return 0

            jax.lax.fori_loop(0, qt, body, 0)

    @pl.when(phase == 1)
    def _score_phase():
        def body(i, _):
            s, row_keys, occupied, hit, way = probe(i)
            row_a = scratch_a[pl.ds(s, 1), :]
            row_b = meta_b_ref[pl.ds(s, 1), :]
            scores = _scores_for_policy(policy, row_keys, row_a, row_b,
                                        q_ref[_Q_TPUT, i])
            scores = jnp.where(occupied, scores, NEG_INF)
            scores = jnp.where(valid_way, scores, POS_INF)
            ord_row, _ = _full_order_row(scores, lane, ways)
            vorder_ref[pl.ds(i, 1), :] = ord_row
            out_ref[_O_HIT, i] = hit.astype(jnp.int32)
            out_ref[_O_WAY, i] = jnp.where(hit, way, 0)
            return 0

        jax.lax.fori_loop(0, qt, body, 0)


@functools.partial(
    jax.jit, static_argnames=("policy", "ways", "qt", "interpret")
)
def kway_fused_probe(
    keys: jnp.ndarray,     # int32 [S, kp]
    fprint: jnp.ndarray,   # int32 [S, kp] 16-bit fingerprints of the keys
    meta_a: jnp.ndarray,   # int32 [S, kp]
    meta_b: jnp.ndarray,   # int32 [S, kp]
    sets: jnp.ndarray,     # int32 [B]
    qkeys: jnp.ndarray,    # int32 [B]
    times_get: jnp.ndarray,  # int32 [B]  t + i
    times_put: jnp.ndarray,  # int32 [B]  t + B + i
    en: jnp.ndarray,       # int32 [B]  1 = live lane (enabled, not padding)
    *,
    policy: int,
    ways: int,
    qt: int = 8,
    interpret: bool | None = None,   # None: repro.kernels.interpret()
):
    """Single-launch fused probe for ``access``: hit decisions plus the full
    victim order scored on the hit-updated metadata (see ``_fused_kernel``).

    Returns (hit int32 [B], way int32 [B], vorder int32 [B, LANES]).  ``hit``
    is the raw probe outcome, unmasked by ``en`` — ``en`` only gates which
    lanes apply their hit-phase metadata transition (disabled and padding
    lanes must not perturb victim scores).
    """
    s, kp = keys.shape
    b = sets.shape[0]
    assert kp == LANES, f"pad ways to {LANES} lanes (got {kp})"
    assert b % qt == 0 and qt % 8 == 0
    if interpret is None:
        interpret = kernels.interpret()
    nt = b // qt

    kernel = functools.partial(
        _fused_kernel,
        policy=policy,
        ways=ways,
        qt=qt,
        empty_key=-1,
    )
    # outputs are written in phase 1 only: phase 0 parks every output block
    # on tile 0 (index p * i), so no unwritten block is ever flushed
    out_tile = pl.BlockSpec((None, 4, qt), lambda p, i: (p * i, 0, 0),
                            memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        kernel,
        grid=(2, nt),
        in_specs=[pl.BlockSpec((None, 5, qt), lambda p, i: (i, 0, 0),
                               memory_space=pltpu.SMEM)]
        + [_whole_vmem()] * 4,
        out_specs=[out_tile,
                   pl.BlockSpec((qt, LANES), lambda p, i: (p * i, 0))],
        scratch_shapes=[pltpu.VMEM((s, kp), jnp.int32)],
        out_shape=[
            jax.ShapeDtypeStruct((nt, 4, qt), jnp.int32),
            jax.ShapeDtypeStruct((b, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="kway_fused_probe",
    )(_query_tiles((sets, qkeys, times_get, times_put, en), qt),
      keys, fprint, meta_a, meta_b)
    hit, way = _untile(outs[0])[:2]
    return hit, way, outs[1]
