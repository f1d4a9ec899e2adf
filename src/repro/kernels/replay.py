"""Pallas TPU megakernel: whole-trace replay in ONE ``pallas_call``.

The paper's throughput headline rests on the cache being a "short continuous
region of memory" that the hot loop keeps close to the cores.  The chunked
replay path (PR 3/4) still round-trips all five state lanes through HBM
between chunks: every chunk is a kernel launch plus an XLA scatter pass.
This kernel retires that split for the replay workload (DESIGN.md §10):

  * the grid iterates over trace *chunks*; the cache state lanes
    (``keys`` / ``fprint`` / ``vals`` / ``meta_a`` / ``meta_b``) live in VMEM
    for the entire trace — they are outputs with a constant index map,
    initialised from the input state on the first grid step and mutated
    in place until the final flush;
  * requests are streamed from HBM via a chunk-indexed BlockSpec into
    SMEM (one ``[F, B]`` block of keys / set ids / enabled flags per grid
    step), so every per-lane read is a scalar load;
  * the per-chunk hit/insert transitions of ``core/kway.apply_access`` are
    applied **in-kernel** (no read-kernel/write-scatter split), bit-identical
    to the chunked-scan replay: hits update metadata sequentially in batch
    order (== the scatter-add/-max), inserts are buffered during victim
    selection so scoring always sees the post-hit / pre-insert state, then
    applied in batch order (== the packed insert scatter);
  * the TinyLFU admission phases (record → peek victim → admit) run
    in-kernel on a VMEM-resident sketch, replicating the batched
    ``admission.record``/``admit`` semantics (pre-chunk doorkeeper reads,
    max-merged counter increments, post-chunk aging);
  * the only per-step outputs are two scalar counters (hits, evictions) —
    one int32 each per chunk, written to SMEM (Mosaic stores no scalar to
    VMEM).

Equivalence contract: for any trace, ``replay_resident`` produces the same
per-chunk hit counts, eviction counts and final state as scanning the same
chunks through ``CacheBackend.access`` (the fused path) with the TinyLFU
phases of ``simulate._replay_batched_scan``.  tests/test_resident.py pins
this across all pallas-supported policies × ±TinyLFU.

Payload convention: the replay workload stores ``val == key`` (as int32),
matching every replay loop in this repo; the kernel derives values from the
key stream instead of carrying a third stream.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.kway import NO_EXPIRY
from repro.core.policies import Policy
from repro.kernels.kway_probe import (LANES, NEG_INF, POS_INF, VMEM_LIMIT,
                                      _fingerprint_i32, _hash_u32,
                                      _scores_for_policy, _whole_vmem)

# Trace/launch tally (same pattern as eval/runner.py): the jitted wrapper
# bumps ("trace", ...) once per XLA compilation and ("launch", ...) once per
# dispatch, so tests can assert "a whole replay is exactly one compile and
# one launch" instead of trusting the docstring.
_TRACE_COUNTS: collections.Counter = collections.Counter()


def trace_counts() -> dict:
    """Compile/launch tally of the replay megakernel, keyed by
    (kind, policy, S, ways, steps, batch, tinylfu)."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


# rows of the per-chunk request block [F, B] (SMEM): the flat kernel reads
# key / set / enabled (+ ttl), the hierarchy kernel key / L1 set / L2 set /
# enabled / ttl
_R_KEY, _R_SET, _R_EN, _R_TTL = 0, 1, 2, 3
_H_KEY, _H_S1, _H_S2, _H_EN, _H_TTL = 0, 1, 2, 3, 4


def _lane_read(row_ref, blane, i):
    """Scalar read of column ``i`` from a [1, Bp] VMEM row ref via a masked
    reduce — no dynamic VMEM addressing, just one VPU select+sum."""
    return jnp.sum(jnp.where(blane == i, row_ref[...], 0))


def _chunk_block(rows, steps: int, batch: int):
    """Stack int32 [T, B] request streams into the [T, F, B] array whose
    [F, B] per-chunk blocks the megakernels read from SMEM."""
    return jnp.stack([r.astype(jnp.int32).reshape(steps, batch)
                      for r in rows], axis=1)


def _counters_spec():
    # per-chunk (hits, evictions), one [1, 2] SMEM block per grid step
    return pl.BlockSpec((None, 1, 2), lambda t, *_: (t, 0, 0),
                        memory_space=pltpu.SMEM)


#: rows per hierarchy L2 DMA: one int32 (8, 128) tile of sublanes
L2_BLOCK = 8


def _row_select(row, lane, idx):
    """Scalar read of column ``idx`` from an in-register [1, N] row."""
    return jnp.sum(jnp.where(lane == idx, row, 0))


def _replay_kernel(
    # scalar prefetch
    scal_ref,            # int32 [2]  (initial clock, initial sketch additions)
    # SMEM input
    q_ref,               # int32 [F, B]  chunk t: key / set / en (/ ttl) rows
    # VMEM inputs
    keys0_ref,           # int32 [S, kp]  initial state lanes
    fpr0_ref,
    vals0_ref,
    ma0_ref,
    mb0_ref,
    *rest,
    policy: int,
    ways: int,
    batch: int,
    tl: tuple | None,    # (width, door_bits, sample) or None
    ttl: bool,           # expiry lane + per-request TTL stream present
    empty_key: int,
):
    # remaining refs: [exp0] + [pk0, dr0] + outputs + scratch — unpack
    # by shape of the static configuration.  With ``ttl`` False nothing
    # TTL-related is in the argument list, so the compiled graph is the
    # pre-expiry kernel verbatim.
    k = 0
    if ttl:
        exp0_ref = rest[k]
        k += 1
    if tl is not None:
        pk0_ref, dr0_ref = rest[k], rest[k + 1]
        k += 2
    cnt_ref = rest[k]
    keys_ref, fpr_ref, vals_ref, ma_ref, mb_ref = rest[k + 1:k + 6]
    k += 6
    if ttl:
        exp_ref = rest[k]
        k += 1
    if tl is not None:
        pk_ref, dr_ref, adds_ref = rest[k], rest[k + 1], rest[k + 2]
        k += 3
    ins_s, ins_w, ins_k, ins_t = rest[k:k + 4]
    k += 4
    if ttl:
        ins_e = rest[k]
        k += 1
    if tl is not None:
        adm_row, pk_new, dr_delta = rest[k], rest[k + 1], rest[k + 2]

    t = pl.program_id(0)
    base = scal_ref[0] + jnp.int32(2 * batch) * t   # chunk t's clock origin
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    valid_way = lane < ways
    bp = ins_s.shape[1]
    blane = jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)

    # ---- first grid step: pull the initial state into the resident buffers
    @pl.when(t == 0)
    def _init():
        keys_ref[...] = keys0_ref[...]
        fpr_ref[...] = fpr0_ref[...]
        vals_ref[...] = vals0_ref[...]
        ma_ref[...] = ma0_ref[...]
        mb_ref[...] = mb0_ref[...]
        if ttl:
            exp_ref[...] = exp0_ref[...]
        if tl is not None:
            pk_ref[...] = pk0_ref[...]
            dr_ref[...] = dr0_ref[...]
            adds_ref[0] = scal_ref[1]

    # ---- chunk-entry expiry scrub (kway.scrub_expired semantics): reclaim
    # every lane whose deadline falls at or before the chunk-exit clock
    # BEFORE any probe, so an expired key is never a hit and its lane
    # scores as empty — the preferred victim.  Reclaim is not an eviction.
    if ttl:
        horizon = base + jnp.int32(2 * batch)
        occ_all = (keys_ref[...] != empty_key) & valid_way
        dead = occ_all & (exp_ref[...] <= horizon)
        keys_ref[...] = jnp.where(dead, empty_key, keys_ref[...])
        fpr_ref[...] = jnp.where(dead, 0, fpr_ref[...])
        vals_ref[...] = jnp.where(dead, 0, vals_ref[...])
        ma_ref[...] = jnp.where(dead, 0, ma_ref[...])
        mb_ref[...] = jnp.where(dead, 0, mb_ref[...])
        exp_ref[...] = jnp.where(dead, NO_EXPIRY, exp_ref[...])

    def probe(s, qk):
        """Probe one set row: fingerprint pre-filter, full-key confirm.
        Returns (hit bool, way i32, row_keys [1,kp], occupied [1,kp])."""
        row_keys = keys_ref[pl.ds(s, 1), :]
        row_fpr = fpr_ref[pl.ds(s, 1), :]
        occupied = (row_keys != empty_key) & valid_way
        qfp = _fingerprint_i32(qk.astype(jnp.uint32))
        eq = (row_fpr == qfp) & (row_keys == qk) & occupied
        hit = jnp.any(eq)
        way = jnp.min(jnp.where(eq, lane, LANES))
        return hit, way, row_keys, occupied

    def masked_scores(row_keys, row_a, row_b, occupied, now):
        sc = _scores_for_policy(policy, row_keys, row_a, row_b, now)
        sc = jnp.where(occupied, sc, NEG_INF)    # empty ways evict first
        return jnp.where(valid_way, sc, POS_INF)  # padding ways never

    # ------------------------------------------------------------------
    # TinyLFU phase A: record the whole chunk (admission.record semantics:
    # doorkeeper reads against the PRE-chunk door, counter increments
    # computed on PRE-chunk counters and max-merged, then one aging check).
    # ------------------------------------------------------------------
    if tl is not None:
        width, door_bits, sample = tl
        wp = pk_ref.shape[1]
        wlane = jax.lax.broadcasted_iota(jnp.int32, (1, wp), 1)
        dp = dr_ref.shape[1]
        dlane = jax.lax.broadcasted_iota(jnp.int32, (1, dp), 1)

        def sketch_pos(key_u32):
            """(door word/bit, per-row counter word/shift) for one key."""
            dh = _hash_u32(key_u32, 0xD00E) & jnp.uint32(door_bits - 1)
            dword = (dh >> 5).astype(jnp.int32)
            dbit = dh & jnp.uint32(31)
            rows = []
            for r in range(4):
                idx = _hash_u32(key_u32, 0xA000 + r) & jnp.uint32(width - 1)
                rows.append(((idx >> 3).astype(jnp.int32),
                             (idx & jnp.uint32(7)) * jnp.uint32(4)))
            return dword, dbit, rows

        def door_bit(dword, dbit):
            cur = _row_select(dr_ref[...], dlane, dword).astype(jnp.uint32)
            return ((cur >> dbit) & jnp.uint32(1)).astype(jnp.int32)

        def estimate(key_u32):
            """admission.estimate on the resident sketch: min over the 4
            count-min rows + the doorkeeper bit."""
            dword, dbit, rows = sketch_pos(key_u32)
            est = jnp.int32(0x7FFFFFFF)
            for r, (word, shift) in enumerate(rows):
                cur = _row_select(pk_ref[pl.ds(r, 1), :], wlane,
                                  word).astype(jnp.uint32)
                nib = ((cur >> shift) & jnp.uint32(0xF)).astype(jnp.int32)
                est = jnp.minimum(est, nib)
            return est + door_bit(dword, dbit)

        dr_delta[...] = jnp.zeros_like(dr_delta)
        pk_new[...] = pk_ref[...]

        def rec_body(i, adds_inc):
            en_i = q_ref[_R_EN, i]
            live = en_i != 0
            key_u = q_ref[_R_KEY, i].astype(jnp.uint32)
            dword, dbit, rows = sketch_pos(key_u)
            in_door = door_bit(dword, dbit) != 0
            # admission.record scatter-SETs ``pre | dmask`` per lane, so for
            # duplicate door words only the LAST enabled lane's bit survives
            # the chunk (the documented batched coalescing).  Overwrite —
            # don't OR — the word's delta to replicate that bit-for-bit.
            bit = (jnp.uint32(1) << dbit).astype(dr_delta.dtype)
            dr_delta[...] = jnp.where((dlane == dword) & live, bit,
                                      dr_delta[...])
            for r, (word, shift) in enumerate(rows):
                row_pre = pk_ref[pl.ds(r, 1), :]
                cur = _row_select(row_pre, wlane, word).astype(jnp.uint32)
                nib = (cur >> shift) & jnp.uint32(0xF)
                do_inc = live & in_door & (nib < jnp.uint32(15))
                neww = cur + (jnp.uint32(1) << shift)
                row_acc = pk_new[pl.ds(r, 1), :]
                upd = (wlane == word) & do_inc
                # the scatter-max of admission.record compares whole words
                # as uint32 — merge in that domain (a set nibble 7 makes the
                # int32 view negative).  Mosaic has no unsigned max: flip
                # the sign bit, take the signed max, flip it back.
                flip = jnp.int32(-0x80000000)
                merged = jnp.maximum(row_acc ^ flip,
                                     neww.astype(jnp.int32) ^ flip) ^ flip
                pk_new[pl.ds(r, 1), :] = jnp.where(upd, merged, row_acc)
            return adds_inc + en_i

        adds_inc = jax.lax.fori_loop(0, batch, rec_body, jnp.int32(0))
        dr_ref[...] = dr_ref[...] | dr_delta[...]
        for r in range(4):
            pk_ref[pl.ds(r, 1), :] = pk_new[pl.ds(r, 1), :]
        adds = adds_ref[0] + adds_inc
        aged = adds >= jnp.int32(sample)
        adds_ref[0] = jnp.where(aged, jnp.int32(0), adds)
        # TinyLFU reset: halve every 4-bit counter, clear the doorkeeper
        halved = jnp.right_shift(
            pk_ref[...].astype(jnp.uint32), jnp.uint32(1)
        ) & jnp.uint32(0x77777777)
        pk_ref[...] = jnp.where(aged, halved.astype(jnp.int32), pk_ref[...])
        dr_ref[...] = jnp.where(aged, jnp.zeros_like(dr_ref), dr_ref[...])

        # ---- TinyLFU phase B: peek each lane's prospective victim on the
        # PRE-hit state at time base+i and gate admission on the
        # post-record sketch (the phase order of the chunked scan).
        def adm_body(i, _):
            qk = q_ref[_R_KEY, i]
            s = q_ref[_R_SET, i]
            hit, _, row_keys, occupied = probe(s, qk)
            row_a = ma_ref[pl.ds(s, 1), :]
            row_b = mb_ref[pl.ds(s, 1), :]
            sc = masked_scores(row_keys, row_a, row_b, occupied, base + i)
            vway = jnp.min(jnp.where(sc == jnp.min(sc), lane, LANES))
            vkey = _row_select(row_keys, lane, vway)
            vvalid = (vkey != empty_key) & ~hit
            ce = estimate(qk.astype(jnp.uint32))
            ve = estimate(vkey.astype(jnp.uint32))
            ok = (~vvalid) | (ce > ve)
            adm_row[...] = jnp.where(blane == i, ok.astype(jnp.int32),
                                     adm_row[...])
            return 0

        jax.lax.fori_loop(0, batch, adm_body, 0)

    # ------------------------------------------------------------------
    # hit phase (apply_access get semantics at times base+i): sequential
    # on_hit transitions == the batched scatter-add (LFU/HYPERBOLIC) and
    # scatter-max (LRU — batch times are increasing).
    # ------------------------------------------------------------------
    def hit_body(i, hits_acc):
        qk = q_ref[_R_KEY, i]
        s = q_ref[_R_SET, i]
        en_i = q_ref[_R_EN, i]
        hit, way, _, _ = probe(s, qk)
        if policy not in (Policy.FIFO, Policy.RANDOM):  # on_hit is identity
            do = hit & (en_i != 0)
            row_a = ma_ref[pl.ds(s, 1), :]
            upd = lane == way            # all-false when way == LANES
            if policy == Policy.LRU:
                new_a = jnp.where(upd, base + i, row_a)
            else:                        # LFU / HYPERBOLIC: count += 1
                new_a = jnp.where(upd, row_a + 1, row_a)
            ma_ref[pl.ds(s, 1), :] = jnp.where(do, new_a, row_a)
        return hits_acc + (hit & (en_i != 0)).astype(jnp.int32)

    hits = jax.lax.fori_loop(0, batch, hit_body, jnp.int32(0))

    # ------------------------------------------------------------------
    # insert phase (apply_access miss semantics at times base+batch+i).
    # Inserts are *buffered*: victim scoring must see the post-hit /
    # pre-insert state (exactly what the batched _victim_order_arrays
    # scores), so the state lanes stay untouched until the apply loop.
    # The buffers double as the conflict resolution of _resolve_inserts:
    #   * dedupe — a key already buffered was this batch's first
    #     occurrence (keys lanes are pre-chunk, so a re-probe cannot see
    #     it; the buffer scan is the CAS-race outcome);
    #   * rank  — the number of buffered inserts into the same set, and
    #     the rank-th lane takes the rank-th worst victim of ITS OWN
    #     victim order (per-lane put timestamps — RANDOM/HYPERBOLIC
    #     orders are time-dependent);
    #   * cap   — rank >= ways lanes are not admitted.
    # ------------------------------------------------------------------
    ins_s[...] = jnp.full_like(ins_s, -1)   # -1 never matches a real set
    ins_k[...] = jnp.full_like(ins_k, -1)   # sanitized keys are never -1

    def ins_body(i, carry):
        n, evs = carry
        qk = q_ref[_R_KEY, i]
        s = q_ref[_R_SET, i]
        en_i = q_ref[_R_EN, i]
        adm_i = (_lane_read(adm_row, blane, i) if tl is not None
                 else jnp.int32(1))
        hit, _, row_keys, occupied = probe(s, qk)
        dup = jnp.any(ins_k[...] == qk)
        rank = jnp.sum((ins_s[...] == s).astype(jnp.int32))
        do = (~hit) & (en_i != 0) & (adm_i != 0) & (~dup) & (rank < ways)

        t_put = base + jnp.int32(batch) + i
        row_a = ma_ref[pl.ds(s, 1), :]
        row_b = mb_ref[pl.ds(s, 1), :]
        work = masked_scores(row_keys, row_a, row_b, occupied, t_put)
        # rank-th worst victim: `ways` rounds of masked min-extraction,
        # keeping the round that matches this lane's rank (ties break
        # toward the lowest lane — the stable argsort of the jnp path).
        vway = jnp.int32(0)
        for r in range(ways):
            m = jnp.min(work)
            w = jnp.min(jnp.where(work == m, lane, LANES))
            vway = jnp.where(jnp.int32(r) == rank, w, vway)
            work = jnp.where(lane == w, POS_INF, work)

        evk = _row_select(row_keys, lane, vway)
        ev = do & (evk != empty_key)

        # buffer slot n (no-op when ~do: the sentinel column Bp matches
        # no lane)
        slot = jnp.where(do, n, jnp.int32(bp))
        sel = blane == slot
        ins_s[...] = jnp.where(sel, s, ins_s[...])
        ins_w[...] = jnp.where(sel, vway, ins_w[...])
        ins_k[...] = jnp.where(sel, qk, ins_k[...])
        ins_t[...] = jnp.where(sel, t_put, ins_t[...])
        if ttl:
            # insert deadline = chunk base + 2B + ttl (kway.insert_deadlines)
            tt_i = q_ref[_R_TTL, i]
            dl = jnp.where(tt_i > 0, base + jnp.int32(2 * batch) + tt_i,
                           jnp.int32(NO_EXPIRY))
            ins_e[...] = jnp.where(sel, dl, ins_e[...])
        return n + do.astype(jnp.int32), evs + ev.astype(jnp.int32)

    n_ins, evs = jax.lax.fori_loop(0, batch, ins_body,
                                   (jnp.int32(0), jnp.int32(0)))

    # ---- apply the buffered inserts in batch order (== the packed insert
    # scatter of apply_access; duplicate (set, way) pairs resolve
    # last-write-wins in batch order, matching the XLA scatter)
    def app_body(j, _):
        live = j < n_ins
        s = jnp.where(live, _lane_read(ins_s, blane, j), 0)
        w = _lane_read(ins_w, blane, j)
        key = _lane_read(ins_k, blane, j)
        t_put = _lane_read(ins_t, blane, j)
        upd = (lane == w) & live
        fp = _fingerprint_i32(key.astype(jnp.uint32))
        # on_insert metadata (policies.on_insert, specialized statically)
        if policy in (Policy.LRU, Policy.FIFO):
            ia, ib = t_put, jnp.int32(0)
        elif policy == Policy.LFU:
            ia, ib = jnp.int32(1), jnp.int32(0)
        elif policy == Policy.RANDOM:
            ia, ib = jnp.int32(0), jnp.int32(0)
        else:                                   # HYPERBOLIC: (n=1, t0=now)
            ia, ib = jnp.int32(1), t_put
        writes = [(keys_ref, key), (fpr_ref, fp), (vals_ref, key),
                  (ma_ref, ia), (mb_ref, ib)]
        if ttl:
            writes.append((exp_ref, _lane_read(ins_e, blane, j)))
        for ref, val in writes:
            row = ref[pl.ds(s, 1), :]
            ref[pl.ds(s, 1), :] = jnp.where(upd, val, row)
        return 0

    jax.lax.fori_loop(0, batch, app_body, 0)

    cnt_ref[0, 0] = hits
    cnt_ref[0, 1] = evs


@functools.partial(
    jax.jit,
    static_argnames=("policy", "ways", "num_sets", "seed", "tl", "ttl",
                     "interpret"))
def _replay_resident_jit(
    keys, fpr, vals, ma, mb, clock,      # state (unpadded [S, ways] lanes)
    chunks, enabled,                     # uint32 [T, B], bool [T, B]
    pk, dr, adds,                        # sketch arrays (dummies when tl None)
    exp, tt,                             # expiry lane + ttl stream (ttl only)
    *,
    policy: int,
    ways: int,
    num_sets: int,
    seed: int,
    tl: tuple | None,                    # (width, door_bits, sample) | None
    ttl: bool,
    interpret: bool,
):
    steps, batch = chunks.shape
    _TRACE_COUNTS[("trace", int(policy), num_sets, ways, steps, batch,
                   tl is not None)] += 1

    # ---- streams: sanitize + route once, one [F, B] SMEM block per chunk
    from repro.core import hashing
    qk = hashing.sanitize_keys(chunks.reshape(-1))
    sets = hashing.set_index(qk, num_sets, seed)
    rows = [qk, sets, enabled]
    if ttl:
        rows.append(tt)
    q = _chunk_block(rows, steps, batch)
    bp = -(-batch // LANES) * LANES        # insert-buffer rows, lane-padded

    # ---- state lanes: pad ways to the LANES register width, bit-cast int32
    def pad_ways(arr, fill):
        s, k = arr.shape
        if k == LANES:
            return arr.astype(jnp.int32)
        return jnp.concatenate(
            [arr.astype(jnp.int32),
             jnp.full((s, LANES - k), fill, jnp.int32)], axis=1)

    keys_i = pad_ways(keys, -1)
    fpr_i = pad_ways(fpr, 0)
    vals_i = pad_ways(vals, 0)
    ma_i = pad_ways(ma, 0)
    mb_i = pad_ways(mb, 0)
    s = keys_i.shape[0]

    scal = jnp.stack([clock.astype(jnp.int32), adds.astype(jnp.int32)])

    kernel = functools.partial(
        _replay_kernel, policy=int(policy), ways=ways, batch=batch,
        tl=tl, ttl=ttl, empty_key=-1)

    lanes_shape = jax.ShapeDtypeStruct((s, LANES), jnp.int32)
    in_arrays = [q, keys_i, fpr_i, vals_i, ma_i, mb_i]
    in_specs = [pl.BlockSpec((None, len(rows), batch),
                             lambda t, *_: (t, 0, 0),
                             memory_space=pltpu.SMEM)] + [_whole_vmem()] * 5
    out_shape = [jax.ShapeDtypeStruct((steps, 1, 2), jnp.int32)] + [
        lanes_shape] * 5
    out_specs = [_counters_spec()] + [_whole_vmem()] * 5
    scratch = [pltpu.VMEM((1, bp), jnp.int32) for _ in range(4)]

    if ttl:
        # expiry lane padded to the register width with NO_EXPIRY (padding
        # ways never expire)
        in_arrays.append(pad_ways(exp, NO_EXPIRY))
        in_specs.append(_whole_vmem())
        out_shape.append(lanes_shape)
        out_specs.append(_whole_vmem())
        scratch.append(pltpu.VMEM((1, bp), jnp.int32))    # ins_e

    if tl is not None:
        pk_i = pk.astype(jnp.int32)
        dr_i = dr.astype(jnp.int32)
        in_arrays += [pk_i, dr_i]
        in_specs += [_whole_vmem(), _whole_vmem()]
        out_shape += [jax.ShapeDtypeStruct(pk_i.shape, jnp.int32),
                      jax.ShapeDtypeStruct(dr_i.shape, jnp.int32),
                      jax.ShapeDtypeStruct((1,), jnp.int32)]
        out_specs += [_whole_vmem(), _whole_vmem(),
                      pl.BlockSpec(memory_space=pltpu.SMEM)]
        scratch += [pltpu.VMEM((1, bp), jnp.int32),       # adm_row
                    pltpu.VMEM(pk_i.shape, jnp.int32),    # pk_new
                    pltpu.VMEM(dr_i.shape, jnp.int32)]    # dr_delta

    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(scal, *in_arrays)

    hits, evs = outs[0][:, 0, 0], outs[0][:, 0, 1]
    keys_f, fpr_f, vals_f, ma_f, mb_f = outs[1:6]
    unpad = lambda a: a[:, :ways]  # noqa: E731
    state_out = (unpad(keys_f).astype(jnp.uint32),
                 unpad(fpr_f).astype(jnp.uint32),
                 unpad(vals_f), unpad(ma_f), unpad(mb_f),
                 clock + jnp.int32(2 * batch * steps))
    idx = 6
    if ttl:
        state_out = state_out + (unpad(outs[idx]),)
        idx += 1
    if tl is not None:
        sketch_out = (outs[idx].astype(jnp.uint32),
                      outs[idx + 1].astype(jnp.uint32),
                      outs[idx + 2][0])
    else:
        sketch_out = None
    return hits, evs, state_out, sketch_out


def replay_resident(
    keys, fpr, vals, ma, mb, clock,
    chunks, enabled,
    *,
    policy: int,
    ways: int,
    num_sets: int,
    seed: int,
    tinylfu=None,                 # TinyLFUConfig | None
    sketch=None,                  # TinyLFUState | None (fresh when None)
    expiry=None,                  # int32 [S, ways] | None
    ttls=None,                    # int32 [T, B] | None
    interpret: bool | None = None,   # None: repro.kernels.interpret()
):
    """Run the replay megakernel: ONE launch for the whole chunked trace.

    ``ttls`` (with the state's ``expiry`` lane) turns on the expiry path
    (DESIGN.md §15): chunk-entry scrub + deadline-stamped inserts, kept in
    a VMEM-resident sixth lane; excludes TinyLFU.  Returns (hits int32
    [steps], evs int32 [steps], (keys, fprint, vals, meta_a, meta_b,
    clock[, expiry]) final state lanes, TinyLFUState' | None).
    """
    from repro.core import admission

    steps, batch = chunks.shape
    ttl = ttls is not None
    if interpret is None:
        interpret = kernels.interpret()
    if ttl:
        if tinylfu is not None:
            raise ValueError(
                "per-request TTLs and TinyLFU admission are mutually "
                "exclusive (the sketch has no expiry-aware semantics)")
        if expiry is None:
            raise ValueError(
                "replay_resident: ttls given but no expiry lane — build "
                "the state with make_cache(cfg, ttl=True)")
    if tinylfu is not None:
        if sketch is None:
            sketch = admission.make_sketch(tinylfu)
        pk, dr, adds = (sketch.packed, sketch.door[None, :],
                        sketch.additions)
        tl = (tinylfu.width, tinylfu.door_bits, tinylfu.sample)
        # pad sketch rows to the 128-lane register width
        wp = -(-pk.shape[1] // LANES) * LANES
        if wp != pk.shape[1]:
            pk = jnp.concatenate(
                [pk, jnp.zeros((pk.shape[0], wp - pk.shape[1]), pk.dtype)],
                axis=1)
        dpad = -(-dr.shape[1] // LANES) * LANES
        dw = dr.shape[1]
        if dpad != dw:
            dr = jnp.concatenate(
                [dr, jnp.zeros((1, dpad - dw), dr.dtype)], axis=1)
    else:
        tl = None
        pk = jnp.zeros((4, LANES), jnp.uint32)
        dr = jnp.zeros((1, LANES), jnp.uint32)
        adds = jnp.zeros((), jnp.int32)
        dw = 0

    _TRACE_COUNTS[("launch", int(policy), num_sets, ways, steps, batch,
                   tinylfu is not None)] += 1
    if ttl:
        exp_in = jnp.asarray(expiry, jnp.int32)
        tt_in = jnp.asarray(ttls, jnp.int32)
    else:
        exp_in = jnp.zeros((), jnp.int32)     # unused dummies (DCE'd)
        tt_in = jnp.zeros((), jnp.int32)
    hits, evs, state_out, sketch_out = _replay_resident_jit(
        keys, fpr, vals, ma, mb, clock, chunks, enabled, pk, dr, adds,
        exp_in, tt_in,
        policy=int(policy), ways=ways, num_sets=num_sets, seed=seed,
        tl=tl, ttl=ttl, interpret=interpret)

    if tinylfu is not None:
        pk_f, dr_f, adds_f = sketch_out
        sketch_out = admission.TinyLFUState(
            packed=pk_f[:, :tinylfu.width // 8],
            door=dr_f[0, :dw], additions=adds_f)
    return hits, evs, state_out, sketch_out


# ===========================================================================
# hierarchical megakernel: VMEM-resident L1 over HBM-resident L2
# ===========================================================================
#
# Past RESIDENT_VMEM_BUDGET the flat kernel above cannot run — its five
# state lanes no longer fit in VMEM.  The hierarchical variant keeps only a
# small high-associativity L1 resident as ONE packed int32 [l1_sets, ROW_W]
# array (five state sections + the scalar mailbox, see core/hierarchy.py)
# and leaves the full L2 in slow memory (``memory_space=ANY``) in the same
# packed layout, so a set's whole row moves in a single DMA.  Per lane the
# kernel runs the SAME four phase transitions as the jnp twin — L1 hit,
# L2 hit/promote, L1 fill, L2 demote — fetching one row, storing its
# replacement, and reading cross-phase scalars back from the stored row's
# mailbox (the in-place-update discipline core/hierarchy.py documents).
# The hot path (L1 hits) touches HBM only for the row round-trips of
# misses — the paper's "short continuous region of memory" argument
# applied to the HBM→VMEM hierarchy itself.
#
# Equivalence contract: bit-identical per-chunk hit/eviction counts and
# final tier states vs ``core/hierarchy.replay_l1_over_l2`` (the jitted
# chunked-scan twin) — pinned by tests/test_hierarchy.py.

def _hier_replay_kernel(
    # scalar prefetch
    scal_ref,            # int32 [1]  initial clock
    # inputs
    q_ref,               # SMEM  [5, B]  chunk t: key / L1 set / L2 set /
    #                                    enabled / ttl (zeros w/o ttl) rows
    l1in_ref,            # VMEM  [S1, ROW_W]  packed L1 rows (initial)
    l2in_ref,            # ANY   [S2, ROW_W]  packed L2 rows (initial)
    # outputs
    cnt_ref,             # SMEM  [1, 2]  per-chunk (hits, evictions)
    l1_ref,              # VMEM  [S1, ROW_W]  packed L1 rows (resident)
    l2out_ref,           # ANY   [S2, ROW_W]  packed L2 rows (resident)
    # scratch
    rowA,                # VMEM [L2_BLOCK, ROW_W]  DMA staging block
    sem,                 # DMA semaphore
    *,
    policy: int,
    l1_ways: int,
    l2_ways: int,
    l2_sets: int,
    seed: int,
    batch: int,
    promote: bool,
    demote: bool,
    ttl: bool,
    interpret: bool,
):
    from repro.core.hierarchy import (SC_DA, SC_DB, SC_DE, SC_DF, SC_DK,
                                      SC_DV, SC_DVALID, SC_EV, SC_HIT1,
                                      SC_L2HIT, SC_PA, SC_PB, SC_PEXP,
                                      SC_PVAL, _l1_fill_row, _l1_hit_row,
                                      _l2_demote_row, _l2_hit_row, _sc_get,
                                      _set_index_i32)

    t = pl.program_id(0)
    base = scal_ref[0] + jnp.int32(2 * batch) * t
    # chunk-exit clock: the lazy-scrub horizon and deadline base (§15)
    hz = base + jnp.int32(2 * batch) if ttl else None

    # ---- first grid step: L1 into VMEM, L2 packed rows into the resident
    # slow-memory buffer (one whole-array DMA)
    @pl.when(t == 0)
    def _init():
        l1_ref[...] = l1in_ref[...]
        cp = pltpu.make_async_copy(l2in_ref, l2out_ref, sem)
        cp.start()
        cp.wait()

    # ---- L2 row glue.  The interpret path indexes the resident ref
    # directly (the emulator charges ~30 µs per DMA op, which would
    # dominate the lane loop); the TPU path stages rows through VMEM
    # scratch with real DMAs.  A DMA of an HBM slice must cover whole
    # (8, 128) tiles, so it moves the aligned block of L2_BLOCK rows that
    # holds set ``s`` and the lane reads / rewrites its own row inside it.
    # ``store`` returns the POST-store row — the lane loop is sequential,
    # and every store follows the fetch of the same set, so on the DMA path
    # the value just written IS the post-store row and the staged block
    # still holds the other rows unchanged.
    if interpret:
        def fetch_l2(s, scratch):
            return l2out_ref[pl.ds(s, 1), :]

        def store_l2(s, scratch, row):
            l2out_ref[pl.ds(s, 1), :] = row
            return l2out_ref[pl.ds(s, 1), :]
    else:
        def block_dma(s, scratch, to_hbm):
            first = pl.multiple_of((s // L2_BLOCK) * L2_BLOCK, L2_BLOCK)
            hbm = l2out_ref.at[pl.ds(first, L2_BLOCK), :]
            src, dst = (scratch, hbm) if to_hbm else (hbm, scratch)
            cp = pltpu.make_async_copy(src, dst, sem)
            cp.start()
            cp.wait()

        def fetch_l2(s, scratch):
            block_dma(s, scratch, to_hbm=False)
            return scratch[pl.ds(s % L2_BLOCK, 1), :]

        def store_l2(s, scratch, row):
            scratch[pl.ds(s % L2_BLOCK, 1), :] = row
            block_dma(s, scratch, to_hbm=True)
            return row

    # ---- sequential lane loop (hierarchy semantics: lane i sees lane
    # i-1's inserts; see core/hierarchy.py).  Lane i runs as steps 2i
    # (phases A+B) and 2i+1 (phases C+D) — the twin's even/odd interleave
    # verbatim, so each step does ONE row round-trip per tier (on the
    # interpret path a second round-trip on the same buffer would
    # re-introduce the defensive full-array copy) and cross-phase scalars
    # ride the loop carry / the stored row's mailbox.
    def body(step, carry):
        hits, evs, hit1_c, l2_c, pval_c, pa_c, pb_c, pexp_c = carry
        i = step >> 1
        is_even = (step & jnp.int32(1)) == 0
        qk = q_ref[_H_KEY, i]
        s1 = q_ref[_H_S1, i]
        s2 = q_ref[_H_S2, i]
        en = q_ref[_H_EN, i] != 0
        fp = _fingerprint_i32(qk.astype(jnp.uint32))
        t_get = base + i
        t_put = base + jnp.int32(batch) + i
        if ttl:
            tt_i = q_ref[_H_TTL, i]
            dl_i = jnp.where(tt_i > 0, hz + tt_i, jnp.int32(NO_EXPIRY))
        else:
            dl_i = None

        # L1 round-trip: phase A (even) / phase C (odd), both on s1
        r1 = l1_ref[pl.ds(s1, 1), :]
        row_a = _l1_hit_row(policy, r1, qk, fp, t_get, en, l1_ways,
                            ttl=ttl, horizon=hz)
        row_c = _l1_fill_row(policy, promote, r1, qk, fp, hit1_c != 0,
                             l2_c != 0, pval_c, pa_c, pb_c, t_put, en,
                             l1_ways, ttl=ttl, horizon=hz, pexp=pexp_c,
                             dl=dl_i)
        l1_ref[pl.ds(s1, 1), :] = jnp.where(is_even, row_a, row_c)
        r1p = l1_ref[pl.ds(s1, 1), :]
        hit1 = _sc_get(r1p, SC_HIT1) != 0       # even-step mailbox
        dvalid = _sc_get(r1p, SC_DVALID) != 0   # odd-step mailbox
        dk = _sc_get(r1p, SC_DK)

        # L2 round-trip: phase B (even, set s2) / phase D (odd, the
        # displaced victim's own set; the even store lands before the odd
        # fetch, so s2v == s2 aliasing reads the post-promote row)
        if demote:
            s2v = _set_index_i32(dk, l2_sets, seed)
            sl2 = jnp.where(is_even, s2, s2v)
        else:
            sl2 = s2
        r2 = fetch_l2(sl2, rowA)
        row_b = _l2_hit_row(policy, promote, r2, qk, fp, hit1, t_get, en,
                            l2_ways, ttl=ttl, horizon=hz)
        if demote:
            df = _sc_get(r1p, SC_DF)
            dv = _sc_get(r1p, SC_DV)
            da = _sc_get(r1p, SC_DA)
            db = _sc_get(r1p, SC_DB)
            de = _sc_get(r1p, SC_DE)
            row_d = _l2_demote_row(policy, r2, dk, df, dv, da, db,
                                   dvalid, t_put, l2_ways,
                                   ttl=ttl, horizon=hz, de=de)
        else:
            row_d = r2                          # odd step: no-op store
        r2p = store_l2(sl2, rowA, jnp.where(is_even, row_b, row_d))
        l2_hit = _sc_get(r2p, SC_L2HIT) != 0
        pval = _sc_get(r2p, SC_PVAL)
        pa = _sc_get(r2p, SC_PA)
        pb = _sc_get(r2p, SC_PB)
        pexp = _sc_get(r2p, SC_PEXP)
        if demote:
            ev = _sc_get(r2p, SC_EV)
        else:
            ev = dvalid.astype(jnp.int32)

        hit = (en & (hit1 | l2_hit)).astype(jnp.int32)
        hits = hits + jnp.where(is_even, hit, 0)
        evs = evs + jnp.where(is_even, jnp.int32(0), ev)
        hit1_c = jnp.where(is_even, hit1.astype(jnp.int32), hit1_c)
        l2_c = jnp.where(is_even, l2_hit.astype(jnp.int32), l2_c)
        pval_c = jnp.where(is_even, pval, pval_c)
        pa_c = jnp.where(is_even, pa, pa_c)
        pb_c = jnp.where(is_even, pb, pb_c)
        pexp_c = jnp.where(is_even, pexp, pexp_c)
        return hits, evs, hit1_c, l2_c, pval_c, pa_c, pb_c, pexp_c

    z = jnp.int32(0)
    hits, evs, *_ = jax.lax.fori_loop(0, 2 * batch, body,
                                      (z, z, z, z, z, z, z, z))
    cnt_ref[0, 0] = hits
    cnt_ref[0, 1] = evs


@functools.partial(
    jax.jit,
    static_argnames=("policy", "l1_ways", "l2_ways", "l1_sets", "l2_sets",
                     "seed", "promote", "demote", "ttl", "carry_exp",
                     "interpret"))
def _replay_hier_jit(
    l1_keys, l1_fpr, l1_vals, l1_ma, l1_mb, l1_exp,  # [S1, l1_ways] lanes
    l2_keys, l2_fpr, l2_vals, l2_ma, l2_mb, l2_exp,  # [S2, l2_ways] lanes
    clock,
    chunks, enabled, tt,                           # uint32/bool/int32 [T, B]
    *,
    policy: int,
    l1_ways: int,
    l2_ways: int,
    l1_sets: int,
    l2_sets: int,
    seed: int,
    promote: bool,
    demote: bool,
    ttl: bool,
    carry_exp: bool,
    interpret: bool,
):
    from repro.core import hashing
    from repro.core.hierarchy import (ROW_W, L1_SEED_SALT, _pack_lanes,
                                      _unpack_expiry, _unpack_lanes)

    steps, batch = chunks.shape
    _TRACE_COUNTS[("trace-hier", int(policy), l1_sets, l1_ways, l2_sets,
                   l2_ways, steps, batch, promote, demote)] += 1

    # ---- streams: sanitize + route BOTH tiers once, one [5, B] SMEM block
    # per chunk
    qk = hashing.sanitize_keys(chunks.reshape(-1))
    s1 = hashing.set_index(qk, l1_sets, seed ^ L1_SEED_SALT)
    s2 = hashing.set_index(qk, l2_sets, seed)
    q = _chunk_block([qk, s1, s2, enabled, tt], steps, batch)

    # ---- both tiers packed [S, ROW_W]: L1 VMEM-resident, L2 row-per-DMA
    l1p = _pack_lanes(l1_keys, l1_fpr, l1_vals, l1_ma, l1_mb, l1_exp)
    l2p = _pack_lanes(l2_keys, l2_fpr, l2_vals, l2_ma, l2_mb, l2_exp)

    scal = clock.astype(jnp.int32).reshape(1)

    kernel = functools.partial(
        _hier_replay_kernel, policy=int(policy), l1_ways=l1_ways,
        l2_ways=l2_ways, l2_sets=l2_sets, seed=seed, batch=batch,
        promote=promote, demote=demote, ttl=ttl, interpret=interpret)

    anyspace = lambda: pl.BlockSpec(memory_space=pl.ANY)  # noqa: E731

    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[pl.BlockSpec((None, 5, batch), lambda t, *_: (t, 0, 0),
                                   memory_space=pltpu.SMEM),
                      _whole_vmem(), anyspace()],
            out_specs=[_counters_spec(), _whole_vmem(), anyspace()],
            scratch_shapes=[pltpu.VMEM((L2_BLOCK, ROW_W), jnp.int32),
                            pltpu.SemaphoreType.DMA],
        ),
        out_shape=[jax.ShapeDtypeStruct((steps, 1, 2), jnp.int32),
                   jax.ShapeDtypeStruct((l1_sets, ROW_W), jnp.int32),
                   jax.ShapeDtypeStruct((l2_sets, ROW_W), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(scal, q, l1p, l2p)

    hits, evs = outs[0][:, 0, 0], outs[0][:, 0, 1]
    clock_f = clock + jnp.int32(2 * batch * steps)
    l1_out = _unpack_lanes(outs[1], l1_ways)
    l2_out = _unpack_lanes(outs[2], l2_ways)
    if carry_exp:
        l1_out = l1_out + (_unpack_expiry(outs[1], l1_ways),)
        l2_out = l2_out + (_unpack_expiry(outs[2], l2_ways),)
    return hits, evs, l1_out, l2_out, clock_f


def replay_hierarchical(
    l1_keys, l1_fpr, l1_vals, l1_ma, l1_mb,
    l2_keys, l2_fpr, l2_vals, l2_ma, l2_mb,
    clock,
    chunks, enabled,
    *,
    policy: int,
    l1_ways: int,
    l2_ways: int,
    l1_sets: int,
    l2_sets: int,
    seed: int,
    promote: bool = True,
    demote: bool = True,
    l1_exp=None,
    l2_exp=None,
    ttls=None,
    interpret: bool | None = None,   # None: repro.kernels.interpret()
):
    """Run the hierarchical replay megakernel: ONE launch, L1 pinned in
    VMEM, L2 in slow memory behind per-set row DMAs.

    ``l1_exp``/``l2_exp`` are optional int32 [S, ways] per-lane expiry
    deadlines; ``ttls`` is an optional int32 [steps, B] per-request TTL
    stream (0 = never expires).  When either is present the expiry lane
    is carried through the kernel (fetched rows are scrubbed at the
    batch-exit horizon before probing — an expired entry is never a hit
    and its lane is the preferred victim) and each tier's returned lane
    tuple gains a sixth expiry member.

    Returns (hits int32 [steps], evs int32 [steps],
    (keys, fprint, vals, meta_a, meta_b[, expiry]) L1 lanes,
    (keys, fprint, vals, meta_a, meta_b[, expiry]) L2 lanes, clock') —
    key/fprint lanes in the int32 bit-cast domain (callers re-cast to
    uint32).
    """
    steps, batch = chunks.shape
    if interpret is None:
        interpret = kernels.interpret()
    if l2_sets % L2_BLOCK:
        raise ValueError(f"replay_hierarchical moves L2 rows in aligned "
                         f"blocks of {L2_BLOCK}; l2_sets={l2_sets} is not a "
                         f"multiple of it")
    _TRACE_COUNTS[("launch-hier", int(policy), l1_sets, l1_ways, l2_sets,
                   l2_ways, steps, batch, promote, demote)] += 1
    carry_exp = (l1_exp is not None or l2_exp is not None
                 or ttls is not None)
    ttl = ttls is not None
    tt = (jnp.zeros((steps, batch), jnp.int32) if ttls is None
          else jnp.asarray(ttls, jnp.int32))
    return _replay_hier_jit(
        l1_keys, l1_fpr, l1_vals, l1_ma, l1_mb, l1_exp,
        l2_keys, l2_fpr, l2_vals, l2_ma, l2_mb, l2_exp, clock,
        jnp.asarray(chunks, jnp.uint32), jnp.asarray(enabled, jnp.bool_),
        tt,
        policy=int(policy), l1_ways=l1_ways, l2_ways=l2_ways,
        l1_sets=l1_sets, l2_sets=l2_sets, seed=seed,
        promote=promote, demote=demote, ttl=ttl, carry_exp=carry_exp,
        interpret=interpret)
