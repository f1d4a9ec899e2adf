"""TinyLFU admission filter — counting Bloom filter + doorkeeper + aging.

The paper pairs LFU eviction (and Hyperbolic) with the TinyLFU admission
policy [17]: a new key is admitted only if its estimated frequency exceeds the
victim's.  We implement the standard construction:

  * a count-min sketch with 4 hash rows of 4-bit saturating counters
    (packed 8 per int32 word for density — same trick as the reference
    implementation's long[] packing),
  * a "doorkeeper" Bloom filter absorbing one-hit wonders,
  * periodic aging: when the sample counter reaches W, every counter is
    halved and the doorkeeper is cleared.

Everything is a fixed-shape pytree, batched over requests, jit-safe.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import hashing

_ROWS = 4


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TinyLFUState:
    packed: jnp.ndarray   # uint32 [ROWS, W/8] — 8 × 4-bit counters per word
    door: jnp.ndarray     # uint32 [DW]        — doorkeeper bloom bits
    additions: jnp.ndarray  # int32 []         — since last aging


@dataclasses.dataclass(frozen=True)
class TinyLFUConfig:
    width: int            # counters per row (power of two)
    door_bits: int        # doorkeeper bits (power of two)
    sample: int           # aging period W (counts of additions)

    def __post_init__(self):
        assert self.width % 8 == 0 and self.width & (self.width - 1) == 0
        assert self.door_bits & (self.door_bits - 1) == 0


def for_capacity(capacity: int) -> TinyLFUConfig:
    """Standard sizing: ~1 counter per cached item × small multiplier."""
    width = max(64, 1 << (capacity - 1).bit_length())
    return TinyLFUConfig(width=width, door_bits=width * 2, sample=capacity * 8)


def make_sketch(cfg: TinyLFUConfig) -> TinyLFUState:
    return TinyLFUState(
        packed=jnp.zeros((_ROWS, cfg.width // 8), jnp.uint32),
        door=jnp.zeros((cfg.door_bits // 32,), jnp.uint32),
        additions=jnp.zeros((), jnp.int32),
    )


def _positions(cfg: TinyLFUConfig, keys: jnp.ndarray):
    """Per row: (word index, nibble shift) for each key. Shapes [ROWS, B]."""
    idx = jnp.stack(
        [
            hashing.hash_u32(keys, seed=0xA000 + r) & jnp.uint32(cfg.width - 1)
            for r in range(_ROWS)
        ]
    )
    word = (idx >> 3).astype(jnp.int32)
    shift = ((idx & jnp.uint32(7)) * jnp.uint32(4)).astype(jnp.uint32)
    return word, shift


def estimate(cfg: TinyLFUConfig, st: TinyLFUState, keys: jnp.ndarray) -> jnp.ndarray:
    """Count-min estimate (+1 if the doorkeeper has the key). int32 [B]."""
    keys = hashing.sanitize_keys(keys)
    word, shift = _positions(cfg, keys)
    rows = jnp.arange(_ROWS)[:, None]
    nib = (st.packed[rows, word] >> shift) & jnp.uint32(0xF)
    est = jnp.min(nib, axis=0).astype(jnp.int32)
    dh = hashing.hash_u32(keys, seed=0xD00E) & jnp.uint32(cfg.door_bits - 1)
    dbit = (st.door[(dh >> 5).astype(jnp.int32)] >> (dh & jnp.uint32(31))) & jnp.uint32(1)
    return est + dbit.astype(jnp.int32)


@partial(jax.jit, static_argnums=0)
def record(cfg: TinyLFUConfig, st: TinyLFUState, keys: jnp.ndarray,
           enabled: Optional[jnp.ndarray] = None) -> TinyLFUState:
    """Record one access per key (batched), under the ``tinylfu.record``
    scope.

    First access goes to the doorkeeper; repeat offenders increment the
    sketch.  Both read the sketch as the batch began: a key enters the
    doorkeeper in one batch and counts from the next.  Saturating 4-bit
    adds, merged per counter *word* by a scatter-max of (word + the lane's
    one-nibble increment): duplicate batch keys coalesce into a single
    increment, and so do distinct keys whose counters share one 32-bit word
    of a row (8 nibbles) in the same batch — the larger of their two
    written words wins, so one of the two increments is lost.  An accepted
    approximation; the serial oracle in tests uses B=1 where semantics are
    exact.  ``enabled`` (bool[B], optional) masks whole lanes: a disabled
    lane touches neither the doorkeeper, the counters, nor the aging tally
    — used for the tail padding of batched replays and the padding lanes
    of the sharded router.  Once ``additions`` reaches ``cfg.sample``
    every counter is halved and the doorkeeper cleared (``_age``).
    """
    with jax.named_scope("tinylfu.record"):
        return _record(cfg, st, keys, enabled)


def _record(cfg: TinyLFUConfig, st: TinyLFUState, keys: jnp.ndarray,
            enabled: Optional[jnp.ndarray]) -> TinyLFUState:
    keys = hashing.sanitize_keys(keys)
    if enabled is None:
        enabled = jnp.ones(keys.shape, jnp.bool_)
    dh = hashing.hash_u32(keys, seed=0xD00E) & jnp.uint32(cfg.door_bits - 1)
    dword = (dh >> 5).astype(jnp.int32)
    dmask = jnp.where(enabled, jnp.uint32(1) << (dh & jnp.uint32(31)),
                      jnp.uint32(0))
    in_door = (st.door[dword] & dmask) != 0

    # Disabled lanes scatter out of bounds (dropped): writing their word
    # back unchanged is NOT a no-op under duplicate indices — a stale
    # rewrite can clobber an enabled lane's fresh bit in the same word.
    dword_w = jnp.where(enabled, dword, jnp.int32(cfg.door_bits // 32))
    door = st.door.at[dword_w].set(st.door[dword] | dmask, mode="drop")

    word, shift = _positions(cfg, keys)          # [ROWS, B]
    rows = jnp.arange(_ROWS)[:, None]
    cur = (st.packed[rows, word] >> shift) & jnp.uint32(0xF)
    not_sat = cur < jnp.uint32(15)
    inc = jnp.where(in_door[None, :] & not_sat, jnp.uint32(1) << shift, jnp.uint32(0))
    # scatter-OR-free: use max-merge per nibble via set of (cur+1)<<shift;
    # duplicates coalesce because the write value is identical per position.
    new_word_val = st.packed[rows, word] + inc
    packed = st.packed.at[rows, word].max(
        jnp.where(inc != 0, new_word_val, jnp.uint32(0))
    )

    additions = st.additions + jnp.sum(enabled.astype(jnp.int32))
    st2 = TinyLFUState(packed=packed, door=door, additions=additions)
    return jax.lax.cond(
        additions >= cfg.sample, lambda s: _age(s), lambda s: s, st2
    )


def _age(st: TinyLFUState) -> TinyLFUState:
    """Halve every 4-bit counter, clear the doorkeeper (TinyLFU reset)."""
    halved = (st.packed >> 1) & jnp.uint32(0x77777777)
    return TinyLFUState(
        packed=halved,
        door=jnp.zeros_like(st.door),
        additions=jnp.zeros_like(st.additions),
    )


@partial(jax.jit, static_argnums=0)
def admit(
    cfg: TinyLFUConfig,
    st: TinyLFUState,
    cand_keys: jnp.ndarray,
    victim_keys: jnp.ndarray,
    victim_valid: jnp.ndarray,
) -> jnp.ndarray:
    """TinyLFU decision: admit iff est(candidate) > est(victim) (or the slot
    is empty).  bool [B]."""
    ce = estimate(cfg, st, cand_keys)
    ve = estimate(cfg, st, victim_keys)
    return (~victim_valid) | (ce > ve)
