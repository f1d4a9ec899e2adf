"""The cache's key hashing, written out for the benchmark's own use.

The set index and the fingerprint are part of what a configuration states
(``seed`` picks the hash), so the references and the warm-fill selection
compute them here, from the published construction: the murmur3 32-bit
finalizer over ``(key + seed * 0x9E3779B1) * 0x85EBCA77``.  Nothing is
imported from the program.

Every function takes an array module ``xp`` (``numpy`` or ``jax.numpy``)
so that the same arithmetic runs on the host for the references and on the
device for the fill selection.
"""
from __future__ import annotations

import numpy as np

EMPTY = 0xFFFFFFFF          # the empty-way sentinel key
SANITIZED = 0xFFFFFFFE      # where a user key equal to EMPTY is folded
FP_SEED = 0xF19E            # fingerprint hash seed
_M = 0xFFFFFFFF


def _u32(xp, v):
    return xp.uint32(v & _M)


def fmix32(xp, x):
    x = x ^ (x >> _u32(xp, 16))
    x = x * _u32(xp, 0x85EBCA6B)
    x = x ^ (x >> _u32(xp, 13))
    x = x * _u32(xp, 0xC2B2AE35)
    return x ^ (x >> _u32(xp, 16))


def hash_u32(xp, keys, seed: int):
    k = keys.astype(xp.uint32)
    return fmix32(xp, (k + _u32(xp, seed * 0x9E3779B1)) * _u32(xp, 0x85EBCA77))


def set_index(xp, keys, num_sets: int, seed: int):
    return (hash_u32(xp, keys, seed) & _u32(xp, num_sets - 1)).astype(xp.int32)


def fingerprint(xp, keys):
    return hash_u32(xp, keys, FP_SEED) & _u32(xp, 0xFFFF)


def sanitize(xp, keys):
    k = keys.astype(xp.uint32)
    return xp.where(k == _u32(xp, EMPTY), _u32(xp, SANITIZED), k)


def fmix32_int(x: int) -> int:
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & _M
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & _M
    return x ^ (x >> 16)

