"""Plain reference of the k-way LRU cache behind TinyLFU admission, on the
host in numpy.

It states the batched semantics directly, a batch of B requests at a time,
from the published contracts: the flat cache's (``flat.py``, DESIGN.md
sections 2, 3 and 8) and TinyLFU's (Einziger, Friedman and Manes, "TinyLFU:
A Highly Efficient Cache Admission Policy", ACM TOS 2017: a doorkeeper
Bloom filter in front of a count-min sketch of 4-bit counters, reset by
halving), in the program's phase order:

1. **record** every request, on the sketch as the batch began:

   * the doorkeeper: one bit a key (hash seed ``0xD00E``) in
     ``door_bits / 32`` words.  A request whose bit was clear sets it; a
     lane writes its word as it was when the batch began with its own bit
     set, so where two lanes of a batch write one word the later lane's
     write stands and the earlier lane's bit is lost;
   * a request whose bit was already set counts once in each of the 4 rows
     (hash seeds ``0xA000 + r``) of ``width`` 4-bit counters, 8 to a 32-bit
     word, saturating at 15.  Each lane writes its word as it was when the
     batch began plus its one increment, and a word written by several
     lanes keeps the largest: duplicate keys count once, and of two
     distinct keys whose counters share a word one increment is lost;
   * each request adds 1 to ``additions``; once it reaches ``sample``,
     every counter is halved, the doorkeeper cleared and ``additions``
     reset to 0;

2. **admit**: a key's estimate is the least of its 4 counters, plus 1 if
   its doorkeeper bit is set, on the sketch after step 1.  Each request's
   victim is way 0 of its set's victim order on the state as the batch
   began (empty ways first, lowest way first, then LRU by the float32
   recency score, ties to the lower way).  A request is admitted iff that
   way is empty, or its key is in the set, or its estimate exceeds the
   victim key's;

3. **access** as ``flat.py`` states it (hits stamp ``clock + i``, the r-th
   first-occurrence insert into a set takes the r-th way of the victim
   order after the hits were stamped, inserts stamp ``clock + B + i``, the
   clock advances by ``2 B``), except that only admitted misses may
   insert: a missed key that is not admitted changes nothing and is not
   counted among its set's inserts.

The control, ``admit_all``, is the same cache with admission off: the
sketch is recorded as above, and every first-occurrence miss inserts.

Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.refs import flat, khash

ROWS = 4
ROW_SEED = 0xA000
DOOR_SEED = 0xD00E
CONTROLS = ("admit_all",)


@dataclasses.dataclass
class TinyLFURefState:
    cache: flat.FlatState
    packed: np.ndarray    # uint32 [ROWS, width / 8]: 8 4-bit counters a word
    door: np.ndarray      # uint32 [door_bits / 32]
    additions: int

    def lanes(self) -> dict:
        """The cache's five lanes and clock, and the sketch's three leaves,
        as the ``kway_tinylfu`` system's ``lanes()`` gives them."""
        return {**self.cache.lanes(), "packed": self.packed, "door": self.door,
                "additions": self.additions}


def init(conf: dict) -> TinyLFURefState:
    """The empty cache of the configuration's geometry and an empty
    sketch of its ``sketch`` sizes."""
    sk = conf["sketch"]
    return TinyLFURefState(flat.init(conf),
                           np.zeros((ROWS, int(sk["width"]) // 8), np.uint32),
                           np.zeros(int(sk["door_bits"]) // 32, np.uint32), 0)


def _door(st: TinyLFURefState, q: np.ndarray):
    """Each key's doorkeeper word and bit mask."""
    dh = khash.hash_u32(np, q, DOOR_SEED) & np.uint32(st.door.size * 32 - 1)
    return (dh >> np.uint32(5)).astype(np.int64), np.uint32(1) << (dh & np.uint32(31))


def _counters(st: TinyLFURefState, q: np.ndarray):
    """Each key's counter word [ROWS, B] and nibble shift [ROWS, B]."""
    width = st.packed.shape[1] * 8
    idx = np.stack([khash.hash_u32(np, q, ROW_SEED + r) & np.uint32(width - 1)
                    for r in range(ROWS)])
    return (idx >> np.uint32(3)).astype(np.int64), (idx & np.uint32(7)) * np.uint32(4)


def estimate(st: TinyLFURefState, keys: np.ndarray) -> np.ndarray:
    """Least of each key's 4 counters, plus its doorkeeper bit."""
    q = khash.sanitize(np, keys)
    word, shift = _counters(st, q)
    nib = (st.packed[np.arange(ROWS)[:, None], word] >> shift) & np.uint32(0xF)
    dword, dmask = _door(st, q)
    return nib.min(axis=0).astype(np.int64) + ((st.door[dword] & dmask) != 0)


def record(st: TinyLFURefState, sample: int, keys: np.ndarray) -> None:
    """Step 1 of the module docstring, for a batch of ``keys``."""
    q = khash.sanitize(np, keys)
    dword, dmask = _door(st, q)
    old = st.door[dword]
    in_door = (old & dmask) != 0
    # the last lane to write each word: the first of the reversed batch
    _, rev = np.unique(dword[::-1], return_index=True)
    last = q.size - 1 - rev
    st.door[dword[last]] = old[last] | dmask[last]

    word, shift = _counters(st, q)
    rows = np.broadcast_to(np.arange(ROWS)[:, None], word.shape)
    cur = st.packed[rows, word]
    count = in_door & (((cur >> shift) & np.uint32(0xF)) < 15)
    np.maximum.at(st.packed, (rows[count], word[count]),
                  cur[count] + (np.uint32(1) << shift[count]))

    st.additions += q.size
    if st.additions >= sample:
        st.packed = (st.packed >> np.uint32(1)) & np.uint32(0x77777777)
        st.door[:] = 0
        st.additions = 0


def victims(cache: flat.FlatState, keys: np.ndarray, seed: int):
    """Each key's victim on ``cache``: way 0 of its set's victim order.
    -> (victim key, whether the victim is a key that the request would
    evict: the way is occupied and the request's key is not in the set)."""
    q = khash.sanitize(np, keys)
    s = khash.set_index(np, q, cache.keys.shape[0], seed)
    row = np.take(cache.keys, s, axis=0)
    present = ((row == q[:, None]) & (row != flat.EMPTY)).any(axis=1)
    score = np.take(cache.meta_a, s, axis=0).astype(np.float32)
    score[row == flat.EMPTY] = flat.NEG
    vkey = row[np.arange(q.size), score.argmin(axis=1)]
    return vkey, (vkey != flat.EMPTY) & ~present


def step(st: TinyLFURefState, conf: dict, keys: np.ndarray, *, control: bool = False):
    """One batch of admitted get-or-insert of ``keys`` with the payload
    convention value == key (as int32), under the configuration's hash
    seed; the control when ``control``.  -> as ``access``."""
    name = conf.get("control", "admit_all")
    if name not in CONTROLS:
        raise ValueError(f"the tinylfu reference has no control {name!r}; "
                         f"have {CONTROLS}")
    seed = int(conf["seed"])
    record(st, int(conf["sketch"]["sample"]), keys)
    vkey, valid = victims(st.cache, keys, seed)
    admit = ~valid | (estimate(st, keys) > estimate(st, vkey))
    if control:
        admit[:] = True
    return access(st.cache, keys, keys.astype(np.int32), seed, admit)


def run(st: TinyLFURefState, conf: dict, batches, *, control: bool = False) -> list:
    """Every batch of ``batches`` in order through ``step``.  Returns per
    batch (hit, vals, evicted, evicted_key)."""
    return [step(st, conf, batches[i], control=control) for i in range(len(batches))]


def access(st: flat.FlatState, qkeys: np.ndarray, qvals: np.ndarray, seed: int,
           admit: np.ndarray):
    """``flat.access`` with only the ``admit`` lanes' misses eligible to
    insert.  Mutates ``st``; returns (hit bool[B], vals int32[B], evicted
    bool[B], evicted_key uint32[B])."""
    b = qkeys.shape[0]
    sets, ways = st.keys.shape
    q = khash.sanitize(np, qkeys)
    s = khash.set_index(np, q, sets, seed)
    row = np.take(st.keys, s, axis=0)
    eq = (row == q[:, None]) & (row != flat.EMPTY)
    hit = eq.any(axis=1)
    way = eq.argmax(axis=1)
    lanes = np.arange(b)
    np.maximum.at(st.meta_a, (s[hit], way[hit]), (st.clock + lanes[hit]).astype(np.int32))
    vals = qvals.astype(np.int32)
    vals[hit] = st.vals[s[hit], way[hit]]

    miss = np.flatnonzero(~hit & admit)
    _, first = np.unique(q[miss], return_index=True)
    ins = np.sort(miss[first])                       # first admitted occurrences
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
    score[keys == flat.EMPTY] = flat.NEG
    n = np.arange(si.size)
    victim = np.argsort(score, axis=1, kind="stable")[n, rank]
    old = keys[n, victim]
    ev = np.zeros(b, bool)
    ek = np.zeros(b, np.uint32)
    ev[ins] = old != flat.EMPTY
    ek[ins] = old

    st.keys[si, victim] = q[ins]
    st.vals[si, victim] = qvals[ins]
    st.meta_a[si, victim] = (st.clock + b + ins).astype(np.int32)
    st.meta_b[si, victim] = 0
    st.clock += 2 * b
    return hit, vals, ev, ek
