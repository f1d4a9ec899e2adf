"""System ``kway_tinylfu``: the flat k-way cache behind TinyLFU admission,
through the program's public API (``CacheBackend`` over ``KWayConfig``,
``core/admission.py``'s sketch) at the configuration's geometry.

The configuration gives the cache's ``num_sets``, ``ways``, ``policy``,
``seed`` and ``backend`` as for ``kway``, and the sketch's ``width``,
``door_bits`` and ``sample`` under ``sketch``.  The state is the pair
(``KWayState``, ``TinyLFUState``).  The warm fill and ``access`` go through
``CacheBackend.access_admitted``, the window's ``replay`` through
``CacheBackend.replay`` with the sketch, which runs that same step; the
value is stored as the key.  ``check`` holds the cache to
``robust.invariants.check_cache`` under that value convention and the
sketch to ``sketch_bits``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.admission import TinyLFUConfig, make_sketch
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig
from repro.core.policies import Policy
from repro.robust.invariants import check_cache, sketch_bits

LANES = ("keys", "fprint", "vals", "meta_a", "meta_b")
SKETCH = ("packed", "door", "additions")


class System:
    """The program's public API at the configuration's geometry, on the
    first of the cell's devices."""

    def __init__(self, conf: dict, devs):
        self.cfg = KWayConfig(num_sets=int(conf["num_sets"]),
                              ways=int(conf["ways"]),
                              policy=Policy.parse(conf["policy"]),
                              seed=int(conf["seed"]))
        sk = conf["sketch"]
        self.tinylfu = TinyLFUConfig(width=int(sk["width"]),
                                     door_bits=int(sk["door_bits"]),
                                     sample=int(sk["sample"]))
        self.capacity = self.cfg.capacity
        self.dev = devs[0]
        self.backend = make_backend(conf["backend"], self.cfg)
        self.filler = make_backend("jnp", self.cfg)
        self._access = jax.jit(self._step)

    def _step(self, state, keys, vals):
        cache, sketch = state
        cache, sketch, hit, out, ek, ev = self.backend.access_admitted(
            cache, sketch, keys, vals, self.tinylfu)
        return (cache, sketch), hit, out, ek, ev

    def fill(self, chunks: np.ndarray):
        """Get-or-insert the host ``chunks`` [n, B] in order into an empty
        cache and sketch through the jnp backend's ``access_admitted``; one
        jitted scan.  Returns ((cache, sketch), evictions during the
        fill)."""
        @jax.jit
        def go(state, chunks):
            def step(st, kk):
                cache, sketch = st
                cache, sketch, _, _, _, ev = self.filler.access_admitted(
                    cache, sketch, kk, kk.astype(jnp.int32), self.tinylfu)
                return (cache, sketch), jnp.sum(ev.astype(jnp.int32))
            st, evs = jax.lax.scan(step, state, chunks)
            return st, jnp.sum(evs)

        return go((self.filler.init(), make_sketch(self.tinylfu)),
                  jax.device_put(chunks, self.dev))

    def replay(self, state, chunks, enabled):
        cache, sketch = state
        hits, evs, cache, sketch = self.backend.replay(
            cache, chunks, enabled, tinylfu=self.tinylfu, sketch=sketch)
        return hits, evs, (cache, sketch)

    def access(self, state, keys, vals):
        return self._access(state, keys, vals)

    def check(self, state) -> int:
        cache, sketch = state
        bits = (check_cache(self.cfg, cache, vals_mode="key").bits
                | sketch_bits(self.tinylfu, sketch))
        return int(jax.device_get(bits))

    @staticmethod
    def occupancy(state) -> int:
        return int(jax.device_get(state[0].occupancy()))

    @staticmethod
    def lanes(state) -> dict:
        cache, sketch = state
        got = jax.device_get({**{n: getattr(cache, n) for n in LANES + ("clock",)},
                              **{n: getattr(sketch, n) for n in SKETCH}})
        return {n: np.asarray(v) for n, v in got.items()}
