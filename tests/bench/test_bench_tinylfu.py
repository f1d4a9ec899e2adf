"""The cache behind TinyLFU admission (``ycsbc_tinylfu``), on the CPU at
small sizes: 64 sets x 8 ways, a sketch of 512 counters a row, aging
every 1000 requests.

* The program's admitted step (``CacheBackend.access_admitted``, through
  the ``kway_tinylfu`` system) equals the plain reference
  (``bench/refs/tinylfu.py``) batch by batch in every output, every cache
  lane and every sketch word: duplicate keys in a batch, two keys whose
  counters share a word, empty and full sets, mixed batches.
* A whole run of the harness on the shrunk cell reads ``correct``; the
  control ``admit_all`` and the three broken timed paths of
  ``test_bench_correct.py`` read incorrect.
* The admission readers (``bench/trace_tinylfu.py``) book ops to the
  outermost ``tinylfu.*`` scope, nameless scatters by data flow, and
  read nothing from a program without the names.
"""
import time
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import bytes_tinylfu, gen, harness, trace_tinylfu
from bench.refs import khash
from repro.core import backend as backend_mod
from test_bench_correct import WINDOW_NUMBERS, _replay_fault, failing
from test_trace_scopes import (SCOPES, TIERED, _ctx, _ev, _hlo_proto,
                               _instruction)

CELL = "ycsbc_tinylfu.read_through"
SKETCH = {"width": 512, "door_bits": 1024, "sample": 1000}
TINY_CONF = {"num_sets": 64, "segment_chunks": 4, "sketch": SKETCH}
TINY_MIX = {"keys": 4096, "items": 3000, "batch": 128}
REF = harness.load_module("refs", "tinylfu")
SYSTEM = harness.load_module("systems", "kway_tinylfu").System


def tiny_cell():
    cell = harness.resolve(CELL)
    cell.config.update(TINY_CONF)
    cell.mix.update(TINY_MIX)
    return cell


def run(cell, seed=2**31 + 11, steps=3):
    return harness.run_cell(cell, seed, 60.0, False, t_start=time.perf_counter(),
                            require_tpu=False, max_steps=steps, log=lambda *a: None)


# ---------------------------------------------------------------------------
# the admitted step against the reference, batch by batch
# ---------------------------------------------------------------------------

def _keys_where(pred, n):
    """The first ``n`` keys from 1 on for which ``pred`` holds."""
    ks = np.arange(1, 200_000, dtype=np.uint32)
    return ks[pred(ks)][:n]


def _batches(conf):
    """Batches that cover what the step decides, in order, each a
    numpy uint32 array (their lengths differ)."""
    sets = int(conf["num_sets"])
    st = REF.init(conf)
    in_set0 = _keys_where(
        lambda k: khash.set_index(np, k, sets, int(conf["seed"])) == 0, 24)
    # two keys whose row-0 counters share a word, at different nibbles,
    # and whose doorkeeper bits lie in different words
    ks = np.arange(1, 4097, dtype=np.uint32)
    (word, shift), (dword, _) = REF._counters(st, ks), REF._door(st, ks)
    b = next(j for j in range(1, 4096) if word[0, j] == word[0, 0]
             and shift[0, j] != shift[0, 0] and dword[j] != dword[0])
    pair = ks[[0, b]]
    rng = np.random.default_rng(3)
    zipf = (rng.zipf(1.3, (40, 128)) % 2500).astype(np.uint32)
    return [
        in_set0[:12],                      # empty set: 8 admitted, 4 past the cap
        in_set0[:12],                      # 8 hits; 4 misses against full-set victims
        np.repeat(in_set0[12:16], 3),      # new keys, each three times
        pair, np.concatenate([pair, pair[::-1], pair]),   # a shared counter word
        *zipf,                             # mixed batches; the sketch ages
    ], pair


def test_access_admitted_matches_reference_batch_by_batch():
    conf = {**harness.resolve(CELL).config, **TINY_CONF}
    system = SYSTEM(conf, jax.devices())
    batches, pair = _batches(conf)
    state, _ = system.fill(np.zeros((0, 128), np.uint32))
    ref = REF.init(conf)
    word, shift = REF._counters(ref, pair)
    ages = rejected = 0
    dword, dmask = REF._door(ref, pair)
    for i, keys in enumerate(batches):
        before = ref.packed[0, word[0, 0]]
        in_door = (ref.door[dword] & dmask) != 0
        vkey, valid = REF.victims(ref.cache, keys, int(conf["seed"]))
        adds = ref.additions
        want = REF.step(ref, conf, keys)
        admit = ~valid | (REF.estimate(ref, keys) > REF.estimate(ref, vkey))
        ages += ref.additions < adds + keys.size
        state, hit, vals, ek, ev = system.access(
            state, jnp.asarray(keys), jnp.asarray(keys.astype(np.int32)))
        got = [np.asarray(x) for x in (hit, vals, ev)]
        for g, w in zip(got, want[:3]):
            np.testing.assert_array_equal(g, w, err_msg=f"batch {i}")
        np.testing.assert_array_equal(np.asarray(ek)[want[2]], want[3][want[2]])
        assert harness.slot_mismatches(system.lanes(state), ref.lanes()) == 0, i
        rejected += int(np.sum(~admit & ~want[0]))
        if i == 4:
            # both keys were in the doorkeeper, so both count: of the two
            # increments to their shared word, the larger write stands
            assert in_door.all()
            grew = [((ref.packed[0, word[0, 0]] >> s) & 0xF) - ((before >> s) & 0xF)
                    for s in shift[0]]
            assert sorted(int(g) for g in grew) == [0, 1], grew
    assert system.check(state) == 0
    assert ages >= 2 and rejected > 0
    full = ref.cache.keys[0] != khash.EMPTY
    assert full.all()


def test_replay_matches_reference():
    """``CacheBackend.replay`` with the sketch, over a zipf stream that
    overflows the cache and ages the sketch three times: per-chunk counts
    and the final cache and sketch equal the reference's, and the control,
    which admits every miss, evicts more."""
    conf = {**harness.resolve(CELL).config, **TINY_CONF}
    system = SYSTEM(conf, jax.devices())
    keys = gen.key_array(5, {**harness.resolve(CELL).mix, **TINY_MIX})
    chunks = keys.reshape(-1, 128)
    state, _ = system.fill(chunks[:8])
    ref = REF.init(conf)
    for c in chunks[:8]:
        REF.step(ref, conf, c)
    hits, evs, state = system.replay(state, jnp.asarray(chunks[8:]),
                                     jnp.ones(chunks[8:].shape, jnp.bool_))
    out = REF.run(ref, conf, chunks[8:])
    np.testing.assert_array_equal(np.asarray(hits), [r[0].sum() for r in out])
    np.testing.assert_array_equal(np.asarray(evs), [r[2].sum() for r in out])
    assert harness.slot_mismatches(system.lanes(state), ref.lanes()) == 0
    ctl = REF.init(conf)
    for c in chunks[:8]:
        REF.step(ctl, conf, c, control=True)
    ctl_out = REF.run(ctl, conf, chunks[8:], control=True)
    assert sum(r[2].sum() for r in ctl_out) > sum(r[2].sum() for r in out)


# ---------------------------------------------------------------------------
# the harness on the shrunk cell
# ---------------------------------------------------------------------------

def test_sound_run_is_correct():
    cell = tiny_cell()
    r = run(cell)
    assert r["correct"], r["checks"]
    assert all(c["value"] == 0 for c in r["checks"].values()), r["checks"]
    assert r["attempted"] == 3 * 4 * 128
    assert 0 < r["window"]["hits"] < r["attempted"]
    assert r["window"]["evictions"] > 0


def test_control_differs_from_reference():
    cell = tiny_cell()
    conf, mix = cell.config, cell.mix
    client = harness.load_module("clients", mix["client"])
    keys = gen.key_array(4, mix)
    fill, _ = harness.reference_fill(REF, conf, harness.fill_chunks(keys, mix["batch"]))
    requests = gen.Cycled(keys, mix["batch"], 24)
    st = harness.copy.deepcopy(fill)
    want = client.reference(REF, conf, st, requests)
    numbers, control = harness.check_window(client, REF, conf, fill, requests,
                                            want, st.lanes(), control=True)
    assert not any(numbers.values()), numbers
    assert control["chunk_mismatches"] > 0 and control["state_mismatches"] > 0


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "answer_altered"])
def test_broken_timed_path_reads_incorrect(kind, monkeypatch):
    monkeypatch.setattr(backend_mod.CacheBackend, "replay", _replay_fault(kind))
    r = run(tiny_cell())
    assert not r["correct"]
    assert set(failing(r)) & WINDOW_NUMBERS, r["checks"]


def test_unknown_control_is_an_error():
    conf = {**harness.resolve(CELL).config, **TINY_CONF, "control": "stale_recency"}
    with pytest.raises(ValueError, match="no control 'stale_recency'"):
        REF.step(REF.init(conf), conf, np.arange(4, dtype=np.uint32))


# ---------------------------------------------------------------------------
# the admission readers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tf_op,scope", [
    ("jit(fn)/while/body/closed_call/jit(record)/tinylfu.record/scatter", "record"),
    ("jit(fn)/while/body/tinylfu.admit/jit(peek_victims)/kway.probe/gather", "admit"),
    ("jit(fn)/tinylfu.admit/tinylfu.record/add", "admit"),       # the outermost
    ("jit(fn)/while/body/jit(apply_access)/kway.insert/scatter", "kway.insert"),
    ("jit(fn)/tinylfu.records/add", None),
    ("", None),
])
def test_scope_is_the_outermost_tinylfu_scope(tf_op, scope):
    assert trace_tinylfu.scope_of(tf_op) == scope


def test_unnamed_sketch_scatter_is_booked_to_record():
    """As a v5e compiles the counters' scatter-max: a nameless fusion whose
    root scatter's indices come from ``tinylfu.record``'s hashing; a
    nameless cache scatter beside it keeps its ``kway.*`` phase, and the
    nameless copy between them goes to the nearer scatter."""
    proto = _hlo_proto([
        (2, 23, [_instruction(20, "p.0", "parameter", parameter=0),
                 _instruction(21, "p.1", "parameter", parameter=1),
                 _instruction(22, "p.2", "parameter", parameter=2),
                 _instruction(23, "scatter.47", "scatter", operands=(20, 21, 22))]),
        (1, 17, [_instruction(10, "packed", "parameter"),
                 _instruction(11, "word", "shift-right-logical",
                              "jit(fn)/jit(record)/tinylfu.record/shift_right_logical"),
                 _instruction(12, "copy.79", "copy", operands=(10,)),
                 _instruction(13, "fusion.161", "fusion", operands=(12, 11, 11),
                              calls=(2,)),
                 _instruction(14, "slot", "select",
                              "jit(fn)/jit(apply_access)/kway.insert/select_n"),
                 _instruction(15, "lane", "parameter"),
                 _instruction(16, "scatter.40", "scatter", operands=(15, 14, 14)),
                 _instruction(17, "copy.90", "copy", operands=(16,))]),
    ])
    assert trace_tinylfu.program_scopes(proto) == {
        "fusion.161": "record", "scatter.47": "record", "copy.79": "record",
        "scatter.40": "kway.insert", "copy.90": "kway.insert"}


def _fake():
    host = _plane("/host:CPU", [("python", [_ev("window", 1000, 10000)])])
    dev = _plane("/device:TPU:0", [
        ("XLA Modules", [_ev("jit_fn(42)", 1500, 6000), _ev("jit_other(7)", 9000, 500)]),
        ("XLA Ops", [_ev("%while.1", 1500, 6000),      # control flow
                     _ev("%fusion.1", 1500, 1000),     # record
                     _ev("%fusion.2", 2500, 500),      # admit's probe
                     _ev("%scatter.3", 3000, 2000),    # nameless: booked record
                     _ev("%fusion.4", 5000, 1000),     # the cache's insert
                     _ev("%fusion.1", 9000, 500)])])   # another program's
    meta = {0: {("%while.1", 42): ("jit(fn)/while", "while", "while.1"),
                ("%fusion.1", 42): ("jit(fn)/while/body/jit(record)/tinylfu.record/add",
                                    "loop fusion", "fusion.1"),
                ("%fusion.2", 42): ("jit(fn)/while/body/tinylfu.admit/kway.probe/eq",
                                    "loop fusion", "fusion.2"),
                ("%scatter.3", 42): ("", "scatter", "scatter.3"),
                ("%fusion.4", 42): ("jit(fn)/while/body/kway.insert/scatter",
                                    "loop fusion", "fusion.4"),
                ("%fusion.1", 7): ("jit(other)/tinylfu.record/add", "loop fusion",
                                   "fusion.1")}}
    return NS(planes=[host, dev]), meta


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=evs) for n, evs in lines])


def test_reduce_books_leaf_ops_of_the_replay_program():
    pd, meta = _fake()
    s = trace_tinylfu.reduce(pd, meta, {42: {"scatter.3": "record"}})
    assert s["record"] == pytest.approx(3000e-9)
    assert s["admit"] == pytest.approx(500e-9)
    assert trace_tinylfu.reduce(pd, meta, {})["record"] == pytest.approx(1000e-9)


def test_readers_read_nothing_from_a_program_without_the_names(tmp_path, monkeypatch):
    """A trace of the cache without admission (the recorded ``getput``
    one, and a tiered one without scopes) gives no value, and raises
    nothing; neither does an untraced run."""
    for fixture in (SCOPES, TIERED):
        ctx = _ctx(tmp_path / fixture.stem, monkeypatch, fixture, "x.read_through",
                   12 * 4096)
        for scope in trace_tinylfu.SCOPES:
            assert trace_tinylfu.scope_us(ctx, scope) is None
    ctx.trace = None
    assert trace_tinylfu.scope_us(ctx, "record") is None
    roofline = harness.load_reader("tinylfu_roofline")
    assert roofline(ctx) is None


def test_bytes_a_request():
    """The flat cache's 180 B at 8 ways, 40 B of record, 20 B of the
    victim's estimate."""
    assert bytes_tinylfu.request_bytes({"ways": 8}) == 240
    assert bytes_tinylfu.request_bytes({"ways": 16}) == 240 + 160
