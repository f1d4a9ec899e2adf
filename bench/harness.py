"""The benchmark harness: one cell, one process, one result line.

Everything specific to a cell is found by name: the configuration file
named in ``BENCHMARK.json`` (``bench/configs/<config>.json``: the
deployment's sizes, the name of its system under test,
``bench/systems/<system>.py``, and of its plain reference,
``bench/refs/<reference>.py``), the traffic mix
(``bench/traffic/<traffic>.json``: generator parameters and the name of
its client, ``bench/clients/<client>.py``), and one reader per metric
(``bench/metrics/<metric>.py``).  The code here is general: set up (draw
the key array, fill the system with the mix's fill stream or that array,
warm the timed call), measure for ``seconds``, then check what the timed
path produced against the plain reference.  It imports nothing of the
program but its compile cache and its degradation events.

A system module gives a class ``System(conf, devs)`` (``devs``: the
cell's devices) with

* ``fill(chunks)``: the warm fill of host chunks [n, B], placed by the
  system itself, in order into an empty state -> (state, evictions
  during the fill);
* ``replay(state, chunks, enabled)`` -> (hits, evictions, state);
* ``access(state, keys, vals)`` -> (state, hit, value, evicted key,
  evicted);
* ``check(state)`` -> the invariant bits of a state, 0 when sound;
* ``occupancy(state)`` -> entries held, and ``capacity``;
* ``lanes(state)`` -> a dict from lane name to numpy array, scalars such
  as the clock included, for ``slot_mismatches``.

The state is whatever pytree the system's own calls take; the harness and
the clients pass it along and never look inside it.  A client module
gives ``SPANS``, ``build``, ``window``, ``requests``, ``reference``,
``outputs``, ``compare`` and ``totals`` (see ``clients/replay.py``); a
reference module gives ``init(conf)``, ``step(state, conf, keys,
control=)`` for one batch and ``run(state, conf, batches, control=)`` for
a window's (``control``: the control the configuration names), and its
state ``lanes()``.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

from bench import gen, trace_reduce
from repro.launch import compile_cache
from repro.robust import events

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"
# A traced run's window: collecting a trace of the replay loop takes the
# profiler about fifteen times the window on a v5e, and a 16 s trace lost
# events, so a traced run measures this much of the steady window.
TRACE_SECONDS = 4.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def _metric_applies(m: dict, cell: str) -> bool:
    return "workloads" not in m or cell in m["workloads"]


def resolve(workload: str, spec: dict | None = None) -> Cell:
    """The cell named ``workload``, with its configuration and mix read
    from their files and the metrics that apply to it."""
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=conf["name"],
        config=json.loads((ROOT / conf["file"]).read_text()),
        mix=json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _metric_applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _metric_applies(m, workload)])


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``: a system, a client, a reference or a
    metric reader, found by its name."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    mod_name = f"bench_{kind}_" + name.replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[mod_name] = mod          # dataclasses look their module up
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return load_module("metrics", name).read


class CompileClock:
    """Counts XLA compiles and persistent-cache reads, as JAX reports
    them through ``jax.monitoring``, and the seconds spent compiling."""

    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def marks(self) -> int:
        return self.compiles + self.cache_hits


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def slot_mismatches(got: dict, want: dict) -> int:
    """How far a system's ``lanes()`` lie from the reference's, over the
    lanes the reference names: the slots where any lane of one entry per
    slot (the shape of ``keys``) differs, plus every element that differs
    in each other lane (the clock).  A lane missing on the system's side,
    or of another shape, counts every element it has."""
    slots = np.shape(want["keys"])
    bad = np.zeros(slots, bool)
    other = 0
    for name, w in want.items():
        w, g = np.asarray(w), got.get(name)
        if g is None or np.shape(g) != w.shape:
            if w.shape == slots:
                bad[...] = True
            else:
                other += w.size
            continue
        g = np.asarray(g)
        if g.dtype.itemsize == w.dtype.itemsize:
            g = g.view(w.dtype)
        if w.shape == slots:
            bad |= g != w
        else:
            other += int(np.sum(g != w))
    return int(bad.sum()) + other


def fill_chunks(keys: np.ndarray, batch: int) -> np.ndarray:
    """The warm fill's requests: every key of the fill's array
    (``gen.fill_keys``) once, in order, in batches of ``batch``."""
    if keys.size % batch:
        raise ValueError(f"the fill's key array ({keys.size}) is not a whole number "
                         f"of batches of {batch}")
    return keys.reshape(-1, batch)


def reference_fill(ref, conf: dict, chunks: np.ndarray):
    """The reference's warm fill.  -> (state, evictions during it)."""
    st = ref.init(conf)
    evs = 0
    for c in chunks:
        evs += int(ref.step(st, conf, c)[2].sum())
    return st, evs


def check_window(client, ref, conf: dict, ref_fill, requests: np.ndarray,
                 got, lanes: dict, control: bool = False):
    """The numbers that decide ``correct`` for the window: outputs and
    final state of the timed path against the reference's, from the
    reference's fill.  With ``control``, also the control's numbers
    against the reference.  -> (numbers, control numbers)."""
    st = copy.deepcopy(ref_fill)
    want = client.reference(ref, conf, st, requests)
    numbers = client.compare(got, want)
    numbers["state_mismatches"] = slot_mismatches(lanes, st.lanes())
    control_numbers = {}
    if control:
        cst = copy.deepcopy(ref_fill)
        control_numbers = client.compare(
            client.reference(ref, conf, cst, requests, control=True), want)
        control_numbers["state_mismatches"] = slot_mismatches(cst.lanes(), st.lanes())
    return numbers, control_numbers


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), with
    every program kept, however quickly it compiled: the kernels compile
    in under a second and would otherwise be compiled on every run."""
    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def devices_for(cell: Cell, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:cell.chips]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             max_steps: int | None = None, control: bool = False,
             log=None) -> dict:
    """Set up, measure for ``seconds`` (``TRACE_SECONDS`` at most when
    ``trace``), check; returns the result object.

    ``max_steps`` caps the window's segments or batches (tests);
    ``control`` also reads the control's numbers against the reference.
    """
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    devs = devices_for(cell, require_tpu)
    t_devices = time.perf_counter()
    clock = CompileClock()
    conf, mix = cell.config, cell.mix
    client = load_module("clients", mix["client"])
    ref = load_module("refs", conf["reference"])
    system = load_module("systems", conf["system"]).System(conf, devs)
    batch = int(mix["batch"])

    # ---- set-up: key array, warm fill, warm-up of the timed call ----------
    marks = [("start", t_start), ("devices", t_devices), ("load", time.perf_counter())]
    keys = gen.key_array(seed, mix)
    traffic = client.build(conf, mix, keys, devs[0])
    fchunks = fill_chunks(gen.fill_keys(seed, mix, keys), batch)
    marks.append(("traffic", time.perf_counter()))
    filled, fill_evs = jax.block_until_ready(system.fill(fchunks))
    marks.append(("fill", time.perf_counter()))
    fill_bits = system.check(filled)
    occupancy = system.occupancy(filled)
    marks.append(("check_cache", time.perf_counter()))
    # warm-up: the window's first call (on the filled state) and a later
    # one (on a state the program returned), whose inputs may differ in
    # placement and so be compiled apart
    warm = client.window(system, filled, traffic, 0.0, math.inf, 2)
    jax.block_until_ready(warm[0])
    del warm
    marks.append(("warm_up", time.perf_counter()))
    events_c0, marks0 = events.cursor(), clock.marks()

    tdir = OUT / "trace" / cell.name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
        jax.profiler.start_trace(str(tdir))

    # ---- the measured window ----------------------------------------------
    # the harness's own bookkeeping allocates per step; no garbage
    # collection of it lands inside some step's latency
    gc.collect()
    gc.disable()
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t_start
    with jax.profiler.TraceAnnotation("window"):
        state, got, lat = client.window(
            system, filled, traffic, t_w0,
            min(seconds, TRACE_SECONDS) if trace else seconds, max_steps)
    window_s = time.perf_counter() - t_w0
    gc.enable()
    stop_s = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    stop_s = time.perf_counter() - stop_s
    in_window = clock.marks() - marks0
    demoted = [f"{ev.component}:{ev.reason}" for ev in events.since(events_c0)]
    steps, per_step = len(got), traffic.per_step
    attempted = steps * per_step
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)

    # ---- free the program's state, then check against the reference --------
    lanes, fill_lanes = system.lanes(state), system.lanes(filled)
    outputs = client.outputs(got)
    requests = client.requests(traffic, steps)
    fill_evs = int(fill_evs)
    del state, filled, traffic, got
    gc.collect()

    t_ref = time.perf_counter()
    ref_fill, ref_fill_evs = reference_fill(ref, conf, fchunks)
    checks = {
        "fill_mismatches": (slot_mismatches(fill_lanes, ref_fill.lanes())
                            + abs(fill_evs - ref_fill_evs)),
        "invariant_violations": fill_bits,
        "window_compiles": in_window,
        "degradation_events": len(demoted),
    }
    numbers, control_checks = check_window(client, ref, conf, ref_fill, requests,
                                           outputs, lanes, control)
    checks.update(numbers)
    ref_s = time.perf_counter() - t_ref
    limits = {name: 0 for name in checks}
    hits, evs = client.totals(outputs)

    log(f"{cell.name}: seed {seed}, {steps} steps of {per_step} "
        f"requests in {window_s:.4f} s; hit ratio {hits / max(attempted, 1):.6f}, "
        f"{evs} evictions; fill occupancy {occupancy}/{system.capacity}, "
        f"{fill_evs} fill evictions; set-up {setup_s:.3f} s "
        f"({clock.seconds:.3f} s compiling, {clock.cache_hits} programs read back; "
        + ", ".join(f"{n} {t - t0:.3f} s" for (_, t0), (n, t)
                    in zip(marks, marks[1:]))
        + f"); reference {ref_s:.3f} s")
    if demoted:
        log(f"degradation events in the window: {demoted}")
    if in_window:
        log(f"{in_window} programs compiled or loaded inside the window: "
            f"this run did not measure its cell")

    ctx = Context(cell=cell, attempted=attempted, window_s=window_s,
                  setup_s=setup_s, latencies_s=lat, device_kind=devs[0].device_kind,
                  trace=None)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if trace:
        t_read = time.perf_counter()
        ctx.trace = trace_reduce.summarize(trace_reduce.load(tdir), len(devs),
                                           ("window",) + tuple(client.SPANS))
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        log(f"trace: {stop_s:.3f} s to stop, {time.perf_counter() - t_read:.3f} s "
            f"to read")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": all(checks[n] <= limits[n] for n in checks),
              "attempted": attempted, "failed": 0, "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = trace_reduce.breakdown(ctx.trace)
    result["window"] = {"hits": hits, "evictions": evs}
    if control:
        result["control"] = control_checks
    result["checks"] = {n: {"value": checks[n], "limit": limits[n]} for n in checks}
    return result


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: Cell
    attempted: int
    window_s: float
    setup_s: float
    latencies_s: list
    device_kind: str
    trace: dict | None
