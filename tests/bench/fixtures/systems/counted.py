"""Test-only system ``counted``: the ``kway`` system with one more state
leaf, the number of requests it has served.  Its state is the pair
(kway state, count), and its ``lanes()`` adds the count as ``served``.
It is one file and one configuration key: the harness finds it by name."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness

kway = harness.load_module("systems", "kway")


@jax.jit
def _served(n, enabled):
    return n + jnp.sum(enabled, dtype=jnp.int32)


class System:
    def __init__(self, conf: dict, devs):
        self.inner = kway.System(conf, devs)
        self.capacity = self.inner.capacity

    def fill(self, chunks: np.ndarray):
        state, evs = self.inner.fill(chunks)
        return (state, jnp.int32(chunks.size)), evs

    def replay(self, state, chunks, enabled):
        hits, evs, st = self.inner.replay(state[0], chunks, enabled)
        return hits, evs, (st, _served(state[1], enabled))

    def access(self, state, keys, vals):
        st, hit, val, ek, ev = self.inner.access(state[0], keys, vals)
        return (st, _served(state[1], jnp.ones(keys.shape, bool))), hit, val, ek, ev

    def check(self, state) -> int:
        return self.inner.check(state[0])

    def occupancy(self, state) -> int:
        return self.inner.occupancy(state[0])

    def lanes(self, state) -> dict:
        return {**self.inner.lanes(state[0]),
                "served": np.asarray(jax.device_get(state[1]))}
