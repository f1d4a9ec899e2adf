"""The benchmark's traffic generator.

A mix file names a generator and gives its parameters; ``key_array``
dispatches on the name.  A mix may also carry a ``fill`` object of the
same shape (a ``generator`` and its parameters): the warm fill's own key
stream (``fill_keys``).  Without one, the fill is the window's key array.

``sequential`` gives ``start + i`` for ``i < keys`` as uint32, as
Caffeine's ``EvictionBenchmark`` puts ``Integer.MIN_VALUE + i`` and then
``key++``.

``ycsb_scrambled_zipfian`` is YCSB's ``ScrambledZipfianGenerator`` with its
published constants, as Caffeine's ``GetPutBenchmark`` draws its keys with
it:

* ``ZipfianGenerator`` (Gray et al., "Quickly generating billion-record
  synthetic databases", SIGMOD 1994), O(1) per draw from a uniform ``u``
  over ``zipf_items`` ranks with the precomputed ``zetan``::

      uz = u * zetan;  rank = 0 if uz < 1, 1 if uz < 1 + 0.5**theta,
      else (long) (zipf_items * (eta * u - eta + 1) ** (1 / (1 - theta)))

* a rank becomes a key as ``fnvhash64(rank) % items`` (YCSB's
  ``Utils.fnvhash64``: FNV-1a over the rank's 8 bytes, low byte first,
  then ``Math.abs``).

The benchmark's setup draws ``keys`` such keys once into an array, and
the requests cycle over it, as ``GetPutBenchmark``'s threads do.  Draws
run on the host in float64 and 64-bit integers, as YCSB's Java does; the
array is small, so this is cheap.
"""
from __future__ import annotations

import math

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def zeta(n: int, theta: float) -> float:
    """sum_{i=1..n} i**-theta: exact below 2**20 terms, Euler-Maclaurin
    with four correction terms above (error far below float64 rounding of
    the sum).  Checks the mix's precomputed ``zetan``."""
    m = min(n, 1 << 20)
    head = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if n == m:
        return head
    t = theta
    f = lambda x: x ** -t                                   # noqa: E731
    d1 = lambda x: -t * x ** (-t - 1)                       # noqa: E731
    d3 = lambda x: -t * (t + 1) * (t + 2) * x ** (-t - 3)   # noqa: E731
    integral = (n ** (1 - t) - m ** (1 - t)) / (1 - t)
    return (head + integral + (f(n) - f(m)) / 2
            + (d1(n) - d1(m)) / 12 - (d3(n) - d3(m)) / 720)


def zipf_pmf(n: int, theta: float) -> np.ndarray:
    """The exact zipf(theta) probabilities of ranks 0..n-1 (for tests)."""
    p = np.arange(1, n + 1, dtype=np.float64) ** -theta
    return p / p.sum()


def zipfian_ranks(u: np.ndarray, items: int, theta: float, zetan: float) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` for each uniform ``u`` in [0, 1):
    int64 ranks in ``[0, items]``."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2 / zetan)
    uz = u * zetan
    r = (float(items) * (eta * u - eta + 1) ** alpha).astype(np.int64)
    r = np.where(uz < 1.0 + 0.5 ** theta, 1, r)
    return np.where(uz < 1.0, 0, r)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` of int64 values: FNV-1a over the 8 bytes,
    low byte first, in wrapping 64-bit arithmetic, then ``Math.abs``."""
    v = v.astype(np.int64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & 0xFF).astype(np.uint64)) * np.uint64(FNV_PRIME_64)
            v = v >> 8
    return np.abs(h.view(np.int64))


def scrambled_zipfian(u: np.ndarray, mix: dict) -> np.ndarray:
    """YCSB ``ScrambledZipfianGenerator(items).nextValue`` for each ``u``."""
    ranks = zipfian_ranks(u, int(mix["zipf_items"]), float(mix["zipfian_constant"]),
                          float(mix["zetan"]))
    return fnvhash64(ranks) % int(mix["items"])


def ycsb_scrambled_zipfian(seed: int, mix: dict) -> np.ndarray:
    u = np.random.default_rng(seed).random(int(mix["keys"]))
    return scrambled_zipfian(u, mix).astype(np.uint32)


def sequential(seed: int, mix: dict) -> np.ndarray:
    """``uint32(start + i)`` for ``i < keys``, wrapping mod 2**32; the seed
    plays no part.  ``start`` = 2**31 gives ``Integer.MIN_VALUE + i`` as
    the cache's 32-bit key.  A key equal to 0xFFFFFFFF, the empty-way
    sentinel, is folded to 0xFFFFFFFE by the cache's ``sanitize`` (and by
    the references'), so a stream that reaches it holds that key twice."""
    start = int(mix["start"]) % (1 << 32)
    return (np.arange(int(mix["keys"]), dtype=np.uint64) + np.uint64(start)).astype(np.uint32)


GENERATORS = {"ycsb_scrambled_zipfian": ycsb_scrambled_zipfian,
              "sequential": sequential}


def key_array(seed: int, mix: dict) -> np.ndarray:
    """The ``keys`` keys of the mix's generator, uint32, from ``seed``: the
    same seed gives the same array, and every seed the same size."""
    draw = GENERATORS.get(mix.get("generator"))
    if draw is None:
        raise ValueError(f"unknown generator {mix.get('generator')!r}; "
                         f"have {sorted(GENERATORS)}")
    return draw(seed, mix)


def fill_keys(seed: int, mix: dict, keys: np.ndarray) -> np.ndarray:
    """The warm fill's keys, in order: the mix's ``fill`` stream where it
    gives one, else ``keys``, the window's own array."""
    return key_array(seed, mix["fill"]) if "fill" in mix else keys


def cycled(keys: np.ndarray, start: int, count: int) -> np.ndarray:
    """``count`` requests read from ``keys`` in order, wrapping around,
    from position ``start``."""
    return keys[(start + np.arange(count)) % keys.size]


class Cycled:
    """``count`` batches of ``batch`` requests read from ``keys`` in order,
    wrapping around, without materializing them: batch ``i`` is batch
    ``i % period``, and only the ``period`` distinct ones are kept."""

    def __init__(self, keys: np.ndarray, batch: int, count: int):
        self.batch, self.count = batch, count
        self.period = keys.size // math.gcd(keys.size, batch)
        self._distinct = [cycled(keys, i * batch, batch)
                          for i in range(min(self.period, count))]

    def __len__(self) -> int:
        return self.count

    def __getitem__(self, i: int) -> np.ndarray:
        if not 0 <= i < self.count:
            raise IndexError(i)
        return self._distinct[i % self.period]
