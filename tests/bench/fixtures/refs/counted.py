"""Test-only reference ``counted``: the ``flat`` reference, counting the
requests it has served in a lane of its own, ``served``, as the
``counted`` system does."""
from __future__ import annotations

import dataclasses

from bench import harness

flat = harness.load_module("refs", "flat")


@dataclasses.dataclass
class CountedState:
    inner: flat.FlatState
    served: int = 0

    def lanes(self) -> dict:
        return {**self.inner.lanes(), "served": self.served}


def init(conf: dict) -> CountedState:
    return CountedState(flat.init(conf))


def step(st: CountedState, conf: dict, keys, *, control: bool = False):
    st.served += keys.size
    return flat.step(st.inner, conf, keys, control=control)


def run(st: CountedState, conf: dict, batches, *, control: bool = False) -> list:
    st.served += len(batches) * batches.batch
    return flat.run(st.inner, conf, batches, control=control)
