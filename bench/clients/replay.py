"""Client ``replay``: drives ``CacheBackend.replay`` over segments of
``segment_chunks`` chunks of ``batch`` requests (the configuration gives
the first, the mix the second), read from the key array in order and
wrapping around, state carried.  Each segment ends in a blocking read of
its per-chunk hit and eviction counts."""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

# the host spans this client opens, by which the trace names idle gaps
SPANS = ("replay_segment", "readback")


@dataclasses.dataclass
class Traffic:
    keys: np.ndarray          # the key array the requests cycle over
    segments: list            # the distinct segments, on the device
    enabled: jax.Array
    per_step: int             # requests per segment
    batch: int


def build(conf: dict, mix: dict, keys: np.ndarray, dev) -> Traffic:
    b, n = int(mix["batch"]), int(conf["segment_chunks"])
    per = n * b
    distinct = keys.size // math.gcd(keys.size, per)
    segs = [jax.device_put(gen.cycled(keys, i * per, per).reshape(n, b), dev)
            for i in range(distinct)]
    enabled = jax.device_put(jnp.ones((n, b), jnp.bool_), dev)
    return Traffic(keys, segs, enabled, per, b)


def window(system, state, traffic: Traffic, t_w0: float, seconds: float,
           max_steps: int | None):
    """-> (state, [(hits, evictions) per segment], no latencies)."""
    got = []
    while True:
        seg = traffic.segments[len(got) % len(traffic.segments)]
        with jax.profiler.TraceAnnotation("replay_segment"):
            h, e, state = system.replay(state, seg, traffic.enabled)
        with jax.profiler.TraceAnnotation("readback"):
            got.append(jax.device_get((h, e)))
        if time.perf_counter() - t_w0 >= seconds or len(got) == max_steps:
            return state, got, []


def requests(traffic: Traffic, steps: int) -> gen.Cycled:
    """The window's requests, as chunks of ``batch``."""
    return gen.Cycled(traffic.keys, traffic.batch,
                      steps * traffic.per_step // traffic.batch)


def reference(ref, conf: dict, st, chunks: gen.Cycled, control: bool = False):
    """Per-chunk (hits, evictions) of the reference (or its control) from
    ``st`` (mutated)."""
    out = ref.run(st, conf, chunks, control=control)
    return (np.array([r[0].sum() for r in out], np.int64),
            np.array([r[2].sum() for r in out], np.int64))


def outputs(got: list):
    """The program's per-chunk (hits, evictions) over the window."""
    return (np.concatenate([np.asarray(h) for h, _ in got]),
            np.concatenate([np.asarray(e) for _, e in got]))


def compare(got, want) -> dict:
    (gh, ge), (wh, we) = got, want
    return {"chunk_mismatches": int(np.sum((gh != wh) | (ge != we)))}


def totals(got) -> tuple:
    """(hits, evictions) of the window."""
    return int(got[0].sum()), int(got[1].sum())
