"""Plain reference of the flat k-way cache, on the host in numpy.

It states the batched semantics directly, a batch of B requests at a time,
from the cache's published contract (DESIGN.md sections 2, 3 and 8):

* every request probes the state as it was when the batch began: a hit is
  a stored key equal to the request's key in the set that the key hashes
  to;
* hits stamp recency ``clock + i`` (lane ``i``; with LRU a slot hit twice
  keeps the later stamp);
* of the requests that missed, the first occurrence of each key inserts;
  the r-th insert into a set (in lane order) takes the r-th way of that
  set's victim order, at most ``ways`` per set per batch;
* the victim order ranks the set's ways after the hits were stamped:
  empty ways first (lowest way first), then by the policy counter as a
  float32 score, ties to the lower way;
* an insert stores key, fingerprint, value, recency ``clock + B + i`` and
  zero; displacing an occupied way is an eviction;
* the clock advances by ``2 B``.

The float32 ranking is the configuration's stated guarantee: recency
stamps above 2**24 round to even, so stamps a few ticks apart can tie and
the lower way goes first.  A control is the same cache with one guarantee
broken; the configuration names it (``control``, ``stale_recency`` where
it names none):

* ``stale_recency``: hits do not refresh recency, so eviction is by
  insertion age and not by LRU;
* ``mru_victims``: the victim order ranks occupied ways most recent
  first, so an insert evicts the newest entry of its set and not the
  oldest.

Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.refs import khash

EMPTY = np.uint32(khash.EMPTY)
NEG = np.float32(-3.0e38)


@dataclasses.dataclass
class FlatState:
    keys: np.ndarray      # uint32 [S, k]
    vals: np.ndarray      # int32  [S, k]
    meta_a: np.ndarray    # int32  [S, k]
    meta_b: np.ndarray    # int32  [S, k]
    clock: int

    @property
    def fprint(self) -> np.ndarray:
        fp = khash.fingerprint(np, self.keys)
        return np.where(self.keys == EMPTY, np.uint32(0), fp)

    def lanes(self) -> dict:
        """The five lanes and the clock, as the ``kway`` system's ``lanes()``
        gives them."""
        return {"keys": self.keys, "fprint": self.fprint, "vals": self.vals,
                "meta_a": self.meta_a, "meta_b": self.meta_b,
                "clock": self.clock}


def init(conf: dict) -> FlatState:
    """The empty cache of the configuration's geometry, clock 0."""
    if conf["policy"] != "LRU":
        raise ValueError(f"the flat reference states LRU, not {conf['policy']!r}")
    sets, ways = int(conf["num_sets"]), int(conf["ways"])
    z = np.zeros((sets, ways), np.int32)
    return FlatState(np.full((sets, ways), EMPTY, np.uint32), z, z.copy(), z.copy(), 0)


def run(st: FlatState, conf: dict, batches, *, control: bool = False) -> list:
    """Every batch of ``batches`` (a ``gen.Cycled``: batch ``i`` repeats
    batch ``i - period``) in order through ``step``.  Returns per batch
    (hit, vals, evicted, evicted_key).

    A period of batches in which every request hits changes no key, value
    or ``meta_b``: it only stamps the recency of the slots it hits and
    advances the clock.  The next period then sends the same requests to
    the same keys, so it hits the same slots at the same lanes and returns
    the same answers, its stamps and clock shifted by the clock's advance
    over one period.  So once two periods in a row have hit everywhere
    (and the second shifted every stamp it moved by exactly that advance),
    the remaining whole periods are applied at once: their answers are the
    last period's, and the moved stamps and the clock advance by that many
    periods.  Everything else is stepped batch by batch."""
    p, n = batches.period, len(batches)
    out, i, quiet, before = [], 0, 0, None
    while i < n:
        if i % p == 0:
            before = (st.meta_a.copy(), st.clock)
        out.append(step(st, conf, batches[i], control=control))
        i += 1
        if i % p:
            continue
        period = out[-p:]
        quiet = quiet + 1 if all(r[0].all() for r in period) else 0
        left = (n - i) // p
        if quiet < 2 or not left:
            continue
        moved = st.meta_a != before[0]
        shift = st.clock - before[1]
        if np.all(st.meta_a[moved].astype(np.int64) - before[0][moved] == shift):
            st.meta_a[moved] += np.int32(left * shift)
            st.clock += left * shift
            out.extend(period * left)
            i += left * p
    return out


CONTROLS = ("stale_recency", "mru_victims")


def step(st: FlatState, conf: dict, keys: np.ndarray, *, control: bool = False):
    """One batch of get-or-insert of ``keys`` with the payload convention
    value == key (as int32), under the configuration's hash seed; the
    configuration's control when ``control``.  -> as ``access``."""
    name = conf.get("control", "stale_recency")
    if name not in CONTROLS:
        raise ValueError(f"the flat reference has no control {name!r}; "
                         f"have {CONTROLS}")
    return access(st, keys, keys.astype(np.int32), int(conf["seed"]),
                  stale_recency=control and name == "stale_recency",
                  mru_victims=control and name == "mru_victims")


def access(st: FlatState, qkeys: np.ndarray, qvals: np.ndarray, seed: int,
           *, stale_recency: bool = False, mru_victims: bool = False):
    """One batch of get-or-insert.  Mutates ``st``; returns (hit bool[B],
    vals int32[B], evicted bool[B], evicted_key uint32[B])."""
    b = qkeys.shape[0]
    sets, ways = st.keys.shape
    q = khash.sanitize(np, qkeys)
    s = khash.set_index(np, q, sets, seed)
    # np.take, not st.keys[s]: the same rows, gathered several times faster
    row = np.take(st.keys, s, axis=0)
    eq = (row == q[:, None]) & (row != EMPTY)
    hit = eq.any(axis=1)
    way = eq.argmax(axis=1)
    lanes = np.arange(b)
    if not stale_recency:
        np.maximum.at(st.meta_a, (s[hit], way[hit]),
                      (st.clock + lanes[hit]).astype(np.int32))
    vals = qvals.astype(np.int32)
    vals[hit] = st.vals[s[hit], way[hit]]

    miss = np.flatnonzero(~hit)
    _, first = np.unique(q[miss], return_index=True)
    ins = np.sort(miss[first])                       # first occurrences
    si = s[ins]
    order = np.argsort(si, kind="stable")
    ss = si[order]
    idx = np.arange(ss.size)
    start = np.maximum.accumulate(
        np.where(np.r_[True, ss[1:] != ss[:-1]], idx, 0)) if ss.size else idx
    rank = np.empty_like(idx)
    rank[order] = idx - start
    keep = rank < ways
    ins, si, rank = ins[keep], si[keep], rank[keep]

    keys = row[ins]                                  # st.keys[si]: no key moved yet
    score = np.take(st.meta_a, si, axis=0).astype(np.float32)
    if mru_victims:
        score = -score
    score[keys == EMPTY] = NEG
    n = np.arange(si.size)
    victim = np.argsort(score, axis=1, kind="stable")[n, rank]
    old = keys[n, victim]
    ev = np.zeros(b, bool)
    ek = np.zeros(b, np.uint32)
    ev[ins] = old != EMPTY
    ek[ins] = old

    st.keys[si, victim] = q[ins]
    st.vals[si, victim] = qvals[ins]
    st.meta_a[si, victim] = (st.clock + b + ins).astype(np.int32)
    st.meta_b[si, victim] = 0
    st.clock += 2 * b
    return hit, vals, ev, ek
