"""What decides a run's ``correct``, on the CPU at small sizes.

* The control (the reference with the stated guarantee that the
  configuration names broken, put in the program's place) differs from
  the reference on every cell's numbers.
* A whole run of the harness, with the chip check skipped and the timed
  path broken underneath, reads ``correct`` false, for each fault a
  one-chip cache cell can have: a step that returns its state unchanged,
  half of each batch left out, an answer altered where it is produced.
  (No cell spans chips yet, so there is no exchange to leave out.)
* Each number that says whether a run measured its cell (the warm fill,
  compiles and demotions in the window) reads above its limit under its
  own fault.
* A system under test is found by its configuration's name, and its
  ``lanes()`` are compared over the lanes the reference names: a
  test-only system with one more state leaf (``fixtures/systems/``) runs
  through the unedited harness, and a fault in that leaf alone fails.
"""
import ast
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, harness
from repro.core import backend as backend_mod
from repro.robust import events

# 64 sets x 8 ways under 1024 keys over 341 items: sets overflow, so the
# window evicts as well as hits
TINY_CONF = {"num_sets": 64, "segment_chunks": 2}
TINY_MIX = {"keys": 1024, "items": 341, "batch": 128}
# evict's shape: 256 sets x 8 ways prepopulated with twice its capacity
# (2^12 keys from 2^31, as the cell fills 2^24), then 2^13 new keys
TINY_EVICT_CONF = {"num_sets": 256, "segment_chunks": 2}
TINY_EVICT_MIX = {"keys": 1 << 13, "batch": 128,
                  "fill": {"generator": "sequential", "start": 2**31, "keys": 1 << 12}}
CELLS = ["getput.read_only", "getput.served", "evict.put_new", "evict.served"]
WINDOW_NUMBERS = {"chunk_mismatches", "lane_mismatches", "state_mismatches"}
KWAY = harness.load_module("systems", "kway").System
FIXTURES = Path(__file__).with_name("fixtures")


def tiny_cell(name):
    cell = harness.resolve(name)
    evict = cell.config_name == "evict"
    cell.config.update(TINY_EVICT_CONF if evict else TINY_CONF)
    cell.mix.update(TINY_EVICT_MIX if evict else TINY_MIX)
    return cell


def run(cell, seed=2**31 + 5, steps=3):
    return harness.run_cell(cell, seed, 60.0, False, t_start=time.perf_counter(),
                            require_tpu=False, max_steps=steps,
                            log=lambda *a: None)


def failing(result):
    return [n for n, c in result["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    cell = tiny_cell(name)
    r = run(cell)
    assert r["correct"], r["checks"]
    per = cell.mix["batch"] * (cell.config["segment_chunks"]
                               if cell.mix["client"] == "replay" else 1)
    assert r["attempted"] == 3 * per
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_differs_from_reference(name):
    cell = tiny_cell(name)
    conf, mix = cell.config, cell.mix
    client = harness.load_module("clients", mix["client"])
    ref = harness.load_module("refs", conf["reference"])
    keys = gen.key_array(4, mix)
    fill, _ = harness.reference_fill(
        ref, conf, harness.fill_chunks(gen.fill_keys(4, mix, keys), mix["batch"]))
    requests = gen.Cycled(keys, mix["batch"], 24)
    st = harness.copy.deepcopy(fill)
    want = client.reference(ref, conf, st, requests)
    numbers, control = harness.check_window(client, ref, conf, fill, requests,
                                            want, st.lanes(), control=True)
    assert not any(numbers.values()), numbers
    assert control["state_mismatches"] > 0
    # a served cell's control also answers differently: other victims
    # (evict) or other hits (getput) than the reference's
    assert control.get("lane_mismatches", 1) > 0, control


@pytest.mark.parametrize("sets", [64, 4096])          # evicting; hit-only
@pytest.mark.parametrize("control", [False, True])
def test_reference_fast_forward_matches_stepping(sets, control):
    """``flat.run`` skips whole periods of hit-only batches; it returns the
    same answers and final state as stepping every batch."""
    conf = {**harness.resolve("getput.read_only").config, "num_sets": sets}
    ref = harness.load_module("refs", "flat")
    keys = gen.key_array(2**31 + 9, {**harness.resolve("getput.read_only").mix,
                                     **TINY_MIX})
    fill, _ = harness.reference_fill(ref, conf, harness.fill_chunks(keys, 128))
    batches = gen.Cycled(keys, 128, 8 * 7 + 3)
    fast, slow = harness.copy.deepcopy(fill), harness.copy.deepcopy(fill)
    got = ref.run(fast, conf, batches, control=control)
    want = [ref.step(slow, conf, batches[i], control=control)
            for i in range(len(batches))]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, b)
    assert harness.slot_mismatches(fast.lanes(), slow.lanes()) == 0
    evicting = sum(int(w[2].sum()) for w in want) > 0
    assert evicting == (sets == 64)


def test_unknown_client_or_reference_is_an_error():
    with pytest.raises(FileNotFoundError, match="no clients named"):
        harness.load_module("clients", "open_loop_imaginary")
    with pytest.raises(FileNotFoundError, match="no refs named"):
        harness.load_module("refs", "tiered_imaginary")
    with pytest.raises(FileNotFoundError, match="no systems named"):
        harness.load_module("systems", "tiered_imaginary")


@pytest.mark.parametrize("system,error", [(None, KeyError),
                                          ("tiered_imaginary", FileNotFoundError)])
def test_configuration_must_name_an_existing_system(system, error):
    """No system is taken by default: a configuration without the key, or
    naming a system that has no module, stops the run before it sets up."""
    cell = tiny_cell("getput.read_only")
    del cell.config["system"]
    if system:
        cell.config["system"] = system
    with pytest.raises(error, match="system"):
        run(cell)


def _replay_fault(kind):
    inner = backend_mod.CacheBackend.replay

    def replay(self, state, chunks, enabled, *a, **k):
        if kind == "half_batch":
            b = enabled.shape[-1]
            enabled = jnp.asarray(enabled) & (jnp.arange(b) < b // 2)
        hits, evs, out, sk = inner(self, state, chunks, enabled, *a, **k)
        if kind == "state_unchanged":
            out = state
        if kind == "answer_altered":
            hits = hits.at[0].add(1)
        return hits, evs, out, sk
    return replay


def _access_fault(kind):
    inner = backend_mod.JnpBackend.access

    def access(self, state, qkeys, qvals, admit_on_miss=None, enabled=None,
               ttls=None, **k):
        timed = not isinstance(qkeys, jax.core.Tracer)   # not the jitted fill
        if timed and kind == "half_batch":
            b = qkeys.shape[0]
            enabled = jnp.arange(b) < b // 2
        out, hit, vals, ek, ev = inner(self, state, qkeys, qvals,
                                       admit_on_miss, enabled, ttls, **k)
        if timed and kind == "state_unchanged":
            out = state
        if timed and kind == "answer_altered":
            vals = vals.at[0].add(1)
        return out, hit, vals, ek, ev
    return access


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_reads_incorrect(name, kind, monkeypatch):
    cell = tiny_cell(name)
    if cell.mix["client"] == "replay":
        monkeypatch.setattr(backend_mod.CacheBackend, "replay", _replay_fault(kind))
    else:
        monkeypatch.setattr(backend_mod.JnpBackend, "access", _access_fault(kind))
    r = run(cell)
    assert not r["correct"]
    assert set(failing(r)) & WINDOW_NUMBERS, r["checks"]


def _fill_fault(kind):
    inner = KWAY.fill

    def fill(self, chunks):
        state, evs = inner(self, chunks)
        if kind == "fill_dropped":
            state = self.filler.init()
        if kind == "fill_reports_evictions":
            evs = evs + 1
        if kind == "fingerprint_corrupted":
            state = dataclasses.replace(state, fprint=state.fprint ^ jnp.uint32(1))
        return state, evs
    return fill


def _replay_side_effect(kind):
    inner = backend_mod.CacheBackend.replay
    calls = []

    def replay(self, state, chunks, enabled, *a, **k):
        calls.append(1)
        if len(calls) > 2:                        # inside the window
            if kind == "compiles_in_window":
                jax.jit(lambda x: x + len(calls))(jnp.zeros(len(calls)))
            if kind == "degrades_in_window":
                events.record(component="test", reason="demoted")
        return inner(self, state, chunks, enabled, *a, **k)
    return replay


@pytest.mark.parametrize("kind,number", [
    ("fill_dropped", "fill_mismatches"),
    ("fill_reports_evictions", "fill_mismatches"),
    ("fingerprint_corrupted", "invariant_violations"),
    ("compiles_in_window", "window_compiles"),
    ("degrades_in_window", "degradation_events"),
])
def test_each_validity_number_reads_its_fault(kind, number, monkeypatch):
    """The numbers that say whether a run measured its cell each read
    above their limit 0 under the fault they exist for."""
    cell = tiny_cell("getput.read_only")
    if kind.startswith(("fill", "fingerprint")):
        monkeypatch.setattr(KWAY, "fill", _fill_fault(kind))
    else:
        monkeypatch.setattr(backend_mod.CacheBackend, "replay",
                            _replay_side_effect(kind))
    r = run(cell)
    assert r["checks"][number]["value"] > r["checks"][number]["limit"]
    assert not r["correct"]


def test_tiny_cell_evicts_in_its_window():
    """The small cells above exercise eviction, not only hits."""
    cell = tiny_cell("getput.read_only")
    conf, mix = cell.config, cell.mix
    ref = harness.load_module("refs", "flat")
    keys = gen.key_array(2**31 + 5, mix)
    st, _ = harness.reference_fill(ref, conf, harness.fill_chunks(keys, mix["batch"]))
    evs = sum(int(ref.step(st, conf, c)[2].sum())
              for c in gen.cycled(keys, 0, 6 * mix["batch"]).reshape(6, -1))
    assert evs > 0


def test_evict_cell_misses_and_evicts_in_its_window():
    """A small cell of evict's shape through the whole harness: every
    check reads 0, no window request hits, and nearly every one evicts,
    since the fill left the sets full."""
    cell = tiny_cell("evict.put_new")
    r = run(cell, steps=4)
    assert r["correct"], r["checks"]
    assert all(c["value"] == 0 for c in r["checks"].values()), r["checks"]
    assert r["window"]["hits"] == 0
    assert r["window"]["evictions"] >= 0.95 * r["attempted"] > 0


@pytest.mark.parametrize("control,bites", [("stale_recency", False),
                                           ("mru_victims", True)])
def test_evict_needs_a_control_on_its_victims(control, bites):
    """Where nothing hits, hits that do not refresh recency change nothing;
    victims taken most recent first change the state."""
    cell = tiny_cell("evict.put_new")
    conf, mix = {**cell.config, "control": control}, cell.mix
    client = harness.load_module("clients", mix["client"])
    ref = harness.load_module("refs", conf["reference"])
    keys = gen.key_array(5, mix)
    fill, _ = harness.reference_fill(
        ref, conf, harness.fill_chunks(gen.fill_keys(5, mix, keys), mix["batch"]))
    requests = gen.Cycled(keys, mix["batch"], 16)
    st = harness.copy.deepcopy(fill)
    want = client.reference(ref, conf, st, requests)
    _, got = harness.check_window(client, ref, conf, fill, requests, want,
                                  st.lanes(), control=True)
    assert (got["state_mismatches"] > 0) == bites, got


def test_unknown_control_is_an_error():
    ref = harness.load_module("refs", "flat")
    conf = {**harness.resolve("evict.put_new").config, "num_sets": 8,
            "control": "imaginary"}
    with pytest.raises(ValueError, match="no control 'imaginary'"):
        ref.step(ref.init(conf), conf, np.arange(4, dtype=np.uint32))


def test_slot_mismatches_counts_slots_and_other_elements():
    """Lanes of one entry per slot count the slots where any differs; any
    other lane counts its differing elements; a lane missing or of another
    shape on the system's side counts every element it has."""
    want = {"keys": np.zeros((4, 2), np.uint32), "vals": np.zeros((4, 2), np.int32),
            "clock": 10, "sketch": np.zeros(6, np.int32)}
    got = {n: np.array(v, copy=True) for n, v in want.items()}
    assert harness.slot_mismatches(got, want) == 0
    got["keys"][0, 0] = 1
    got["vals"][0, 0] = 1                     # the same slot as the key
    got["vals"][3, 0] = 1
    assert harness.slot_mismatches(got, want) == 2
    got["clock"] = np.int32(11)
    got["sketch"][:2] = 5
    assert harness.slot_mismatches(got, want) == 2 + 1 + 2
    got["extra"] = np.ones(3)                 # a lane the reference does not name
    assert harness.slot_mismatches(got, want) == 5
    del got["sketch"]
    assert harness.slot_mismatches(got, want) == 2 + 1 + 6
    got["vals"] = np.zeros((2, 4), np.int32)
    assert harness.slot_mismatches(got, want) == 8 + 1 + 6


@pytest.fixture
def fixture_bench(tmp_path, monkeypatch):
    """The benchmark's tree by links, with the test-only systems and
    references beside the real ones: the harness, unedited, finds them by
    the configuration's names."""
    root = tmp_path / "bench"
    root.mkdir()
    for kind in ("clients", "metrics", "traffic"):
        (root / kind).symlink_to(harness.BENCH / kind)
    for kind in ("systems", "refs"):
        (root / kind).mkdir()
        for src in [*(harness.BENCH / kind).glob("*.py"), *(FIXTURES / kind).glob("*.py")]:
            (root / kind / src.name).symlink_to(src)
    monkeypatch.setattr(harness, "BENCH", root)


def counted_cell(name):
    cell = tiny_cell(name)
    cell.config.update(system="counted", reference="counted")
    return cell


@pytest.mark.parametrize("name", ["getput.read_only", "getput.served"])
def test_a_new_system_runs_through_the_harness(name, fixture_bench):
    r = run(counted_cell(name))
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("name", ["getput.read_only", "getput.served"])
def test_a_fault_in_a_system_leaf_alone_fails(name, fixture_bench, monkeypatch):
    """The served count gains one more on each window call; every key,
    value and answer is right."""
    counted = harness.load_module("systems", "counted")
    monkeypatch.setattr(counted, "_served",
                        jax.jit(lambda n, enabled: n + jnp.sum(enabled, dtype=jnp.int32) + 1))
    r = run(counted_cell(name))
    assert not r["correct"]
    assert failing(r) == ["state_mismatches"], r["checks"]
    assert r["checks"]["state_mismatches"]["value"] == 1


def test_harness_imports_nothing_of_the_cache():
    """The system under test lives in ``bench/systems/``; the harness keeps
    of the program only its compile cache and its degradation events."""
    tree = ast.parse(Path(harness.__file__).read_text())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    names |= {f"{n.module}.{a.name}" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom) for a in n.names}
    assert {n for n in names if n.startswith("repro")} == {
        "repro.launch.compile_cache", "repro.robust.events"}
