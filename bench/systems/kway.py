"""System ``kway``: the flat k-way cache through the program's public API
(``CacheBackend`` over ``KWayConfig``) at the configuration's geometry.

The configuration gives ``num_sets``, ``ways``, ``policy``, ``seed`` (the
cache's hash seed) and ``backend``, which serves the window's ``replay``
and ``access``.  The warm fill goes through the jnp backend's ``access``
with the value stored as the key and no admission; ``check`` holds the
state to ``robust.invariants.check_cache`` under that value convention.
The state is the program's ``KWayState``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import make_backend
from repro.core.kway import KWayConfig
from repro.core.policies import Policy
from repro.robust.invariants import check_cache

LANES = ("keys", "fprint", "vals", "meta_a", "meta_b")


class System:
    """The program's public API at the configuration's geometry, on the
    first of the cell's devices."""

    def __init__(self, conf: dict, devs):
        self.cfg = KWayConfig(num_sets=int(conf["num_sets"]),
                              ways=int(conf["ways"]),
                              policy=Policy.parse(conf["policy"]),
                              seed=int(conf["seed"]))
        self.capacity = self.cfg.capacity
        self.dev = devs[0]
        self.backend = make_backend(conf["backend"], self.cfg)
        self.filler = make_backend("jnp", self.cfg)

    def fill(self, chunks: np.ndarray):
        """Get-or-insert the host ``chunks`` [n, B] in order into an empty
        cache through the jnp backend's ``access``; one jitted scan.
        Returns (state, evictions during the fill)."""
        @jax.jit
        def go(state, chunks):
            def step(st, kk):
                st, _, _, _, ev = self.filler.access(st, kk, kk.astype(jnp.int32))
                return st, jnp.sum(ev.astype(jnp.int32))
            st, evs = jax.lax.scan(step, state, chunks)
            return st, jnp.sum(evs)

        return go(self.filler.init(), jax.device_put(chunks, self.dev))

    def replay(self, state, chunks, enabled):
        hits, evs, state, _ = self.backend.replay(state, chunks, enabled)
        return hits, evs, state

    def access(self, state, keys, vals):
        return self.backend.access(state, keys, vals)

    def check(self, state) -> int:
        return int(jax.device_get(check_cache(self.cfg, state,
                                              vals_mode="key").bits))

    @staticmethod
    def occupancy(state) -> int:
        return int(jax.device_get(state.occupancy()))

    @staticmethod
    def lanes(state) -> dict:
        got = jax.device_get({n: getattr(state, n) for n in LANES + ("clock",)})
        return {n: np.asarray(v) for n, v in got.items()}
