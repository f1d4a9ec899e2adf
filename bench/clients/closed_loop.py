"""Client ``closed_loop``: one client with one batch in flight.  Each batch
is ``batch`` host-side keys, read from the key array in order and wrapping
around; it goes to the device, through ``CacheBackend.access``, and its
hits, values and evictions come back to the host before the next batch
is sent.  The latency of a batch is that whole round trip."""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import numpy as np

from bench import gen

# the host spans this client opens, by which the trace names idle gaps
SPANS = ("h2d", "access", "readback")


@dataclasses.dataclass
class Traffic:
    keys: np.ndarray          # the key array the requests cycle over
    batches: list             # the distinct host batches (keys, values)
    dev: object
    per_step: int


def build(conf: dict, mix: dict, keys: np.ndarray, dev) -> Traffic:
    b = int(mix["batch"])
    distinct = keys.size // math.gcd(keys.size, b)
    batches = []
    for i in range(distinct):
        k = gen.cycled(keys, i * b, b)
        batches.append((k, k.astype(np.int32)))
    return Traffic(keys, batches, dev, b)


def window(system, state, traffic: Traffic, t_w0: float, seconds: float,
           max_steps: int | None):
    """-> (state, [(hit, value, evicted key, evicted) per batch],
    [latency s per batch])."""
    got, lat = [], []
    while True:
        t0 = time.perf_counter()
        k, v = traffic.batches[len(got) % len(traffic.batches)]
        with jax.profiler.TraceAnnotation("h2d"):
            k, v = jax.device_put(k, traffic.dev), jax.device_put(v, traffic.dev)
        with jax.profiler.TraceAnnotation("access"):
            state, hit, val, ek, ev = system.access(state, k, v)
        with jax.profiler.TraceAnnotation("readback"):
            got.append(jax.device_get((hit, val, ek, ev)))
        lat.append(time.perf_counter() - t0)
        if time.perf_counter() - t_w0 >= seconds or len(got) == max_steps:
            return state, got, lat


def requests(traffic: Traffic, steps: int) -> gen.Cycled:
    """The window's requests, as its batches."""
    return gen.Cycled(traffic.keys, traffic.per_step, steps)


def reference(ref, conf: dict, st, batches: gen.Cycled, control: bool = False):
    """Per-batch (hit, value, evicted key, evicted) lanes of the reference
    (or its control) from ``st`` (mutated), in the order ``access``
    returns them."""
    return [(hit, val, ek, ev)
            for hit, val, ev, ek in ref.run(st, conf, batches, control=control)]


def outputs(got: list) -> list:
    return [tuple(np.asarray(x) for x in g) for g in got]


def compare(got: list, want: list) -> dict:
    """Lanes where the hit, the value, the eviction flag or (where an
    eviction happened) the evicted key differ."""
    bad = 0
    for (gh, gv, gek, gev), (wh, wv, wek, wev) in zip(got, want, strict=True):
        bad += int(((gh != wh) | (gv != wv) | (gev != wev) | (wev & (gek != wek))).sum())
    return {"lane_mismatches": bad}


def totals(got: list) -> tuple:
    """(hits, evictions) of the window."""
    return int(sum(g[0].sum() for g in got)), int(sum(g[3].sum() for g in got))
