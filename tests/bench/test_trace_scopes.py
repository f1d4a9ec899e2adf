"""The scope reduction (``bench/trace_scopes.py``): the XPlane metadata
decoder, the booking of scatters the compiler leaves without a name, the
attribution of leaf ops to ``kway.*`` phases and of idle time to the
``cache.access`` span, and all of them on recorded chip traces.

``traces/getput_scopes.xplane.pb`` was recorded on a TPU v5e by running
this file as a script from the repository root (``python3
tests/bench/test_trace_scopes.py <out dir>``): three replay segments of
four 4096-request chunks and twenty served ``access`` batches on
``getput``'s geometry, under one ``window`` span.  Its ``/host:metadata``
plane keeps only the replay program's HLO, and of that only what the
reduction reads (``slim``).  It lives apart from ``fixtures/``, whose
newest file ``trace_reduce.load`` reads.

``traces/evict_replay.hlo.pb`` is the replay program's ``HloProto`` at
``evict``'s geometry (2^20 sets x 8 ways, 64 chunks a call), taken from the
``/host:metadata`` plane of a traced ``evict.put_new`` run on a TPU v5e
and cut the same way.
"""
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

if __name__ == "__main__":      # run as a script: the repo's packages
    _ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
from bench import trace_scopes  # noqa: E402

HERE = Path(__file__).resolve().parent
TIERED = HERE / "fixtures" / "kv_tiered_1s.xplane.pb"
SCOPES = HERE / "traces" / "getput_scopes.xplane.pb"
NODE_HLO = HERE / "traces" / "evict_replay.hlo.pb"


# ---------------------------------------------------------------------------
# the metadata decoder
# ---------------------------------------------------------------------------

def test_decoder_reads_tf_op_of_a_recorded_trace():
    ops = trace_scopes.op_metadata(TIERED)[0]
    key = next(k for k in ops if k[0].startswith("%fusion.7 = "))
    tf_op, category, display = ops[key]
    assert tf_op == "jit(_replay_hier_jit)/convert_element_type:"
    assert (category, display, key[1]) == (
        "loop fusion", "fusion.7", 14229378553879300230)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(field: int, body: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _xspace(stat_names: dict, events: list) -> bytes:
    """An XSpace with one TPU plane: stat metadata ``{id: name}`` and event
    metadata ``[(id, name, [(stat id, field, value)])]``."""
    plane = _len(2, b"/device:TPU:0")
    for sid, name in stat_names.items():
        plane += _len(5, _int(1, sid) + _len(2, _int(1, sid) + _len(2, name.encode())))
    for eid, name, stats in events:
        md = _int(1, eid) + _len(2, name.encode())
        for sid, field, value in stats:
            body = _int(1, sid)
            body += (_len(field, value.encode()) if isinstance(value, str)
                     else _int(field, value))
            md += _len(5, body)
        plane += _len(4, _int(1, eid) + _len(2, md))
    plane += _len(3, b"\x12\x04skip")      # a line, skipped by its length
    return _len(1, _len(2, b"/host:CPU")) + _len(1, plane)


def test_decoder_reads_tf_op_given_as_a_reference(tmp_path):
    """``tf_op`` may be a ``ref_value`` naming a stat metadata entry."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(
        {1: "tf_op", 2: "hlo_category", 3: "program_id",
         9: "jit(fn)/while/body/kway.hit/gather"},
        [(5, "%fusion.3 = s32[8]", [(1, 7, 9), (2, 5, "loop fusion"),
                                     (3, 3, 42)]),
         (6, "%sort.1 = s32[8]", [(1, 5, "jit(fn)/kway.resolve/sort:")])]))
    ops = trace_scopes.op_metadata(path)[0]
    assert ops[("%fusion.3 = s32[8]", 42)][:2] == (
        "jit(fn)/while/body/kway.hit/gather", "loop fusion")
    assert ops[("%sort.1 = s32[8]", None)][0] == "jit(fn)/kway.resolve/sort:"


@pytest.mark.parametrize("tf_op,phase", [
    ("jit(fn)/while/body/closed_call/jit(_access_fused)/kway.probe/add:", "probe"),
    ("jit(fn)/jit(apply_access)/kway.resolve/jit(_where)/select_n", "resolve"),
    ("jit(f)/shard.access/kway.hit/kway.insert/scatter", "insert"),
    ("jit(fn)/while/body/reduce_sum", "other"),
    ("jit(fn)/kway.probes/add", "other"),
    ("", "other"),
])
def test_phase_is_the_innermost_kway_scope(tf_op, phase):
    assert trace_scopes.phase_of(tf_op) == phase


# ---------------------------------------------------------------------------
# scatters the compiler leaves without a name
# ---------------------------------------------------------------------------

def _instruction(iid, name, opcode, op_name="", operands=(), calls=(), parameter=0):
    body = _len(1, name.encode()) + _len(2, opcode.encode())
    if op_name:
        body += _len(7, _len(2, op_name.encode()))
    body += _int(9, parameter) + _int(35, iid)
    if operands:
        body += _len(36, b"".join(_varint(o) for o in operands))   # packed
    for c in calls:
        body += _int(38, c)                                        # not packed
    return body


def _hlo_proto(computations) -> bytes:
    """An ``HloProto`` of ``[(computation id, root id, [instruction])]``."""
    module = b""
    for cid, root, instructions in computations:
        comp = b"".join(_len(2, i) for i in instructions) + _int(5, cid) + _int(6, root)
        module += _len(3, comp)
    return _len(1, module)


def test_unnamed_scatter_is_booked_by_its_indices():
    """As a v5e compiles a 2-D lane scatter: a fusion and its root scatter
    carry no name, the updates come from one scope and the indices, through
    a nameless fusion, from another; the indices decide."""
    proto = _hlo_proto([
        (2, 23, [_instruction(20, "p.0", "parameter", parameter=0),
                 _instruction(21, "p.1", "parameter", parameter=1),
                 _instruction(22, "p.2", "parameter", parameter=2),
                 _instruction(23, "scatter.84", "scatter", operands=(20, 21, 22))]),
        (3, 31, [_instruction(30, "p.3", "parameter"),
                 _instruction(31, "select.4", "select", operands=(30,))]),
        (1, 16, [_instruction(10, "concatenate.1", "concatenate",
                              "jit(f)/kway.insert/concatenate"),
                 _instruction(11, "select_fusion", "fusion", operands=(10,), calls=(3,)),
                 _instruction(13, "lane", "parameter"),
                 _instruction(14, "select_n.2", "select",
                              "jit(f)/kway.probe/jit(_where)/select_n"),
                 _instruction(12, "fusion.83", "fusion", operands=(13, 11, 14),
                              calls=(2,)),
                 _instruction(15, "scatter.5", "scatter", "jit(f)/kway.resolve/scatter",
                              operands=(13, 14, 14)),
                 # indices with no scoped feeder: left to ``other``
                 _instruction(16, "scatter.6", "scatter", operands=(12, 13, 14))]),
    ])
    assert trace_scopes.scatter_phases(proto) == {
        "fusion.83": "insert", "scatter.84": "insert"}


def test_layout_ops_go_to_the_nearest_booked_scatter():
    """Nameless ops tied to a booked scatter through other nameless ops
    take its phase: the copy that lays a lane out for it, the loop that
    lays its result back out and that loop's body.  A named op, a
    constant and a parameter join nothing, so the scan's own named
    bookkeeping and what lies beyond it stay ``other``."""
    proto = _hlo_proto([
        # the scatter fusion's computation
        (2, 23, [_instruction(20, "p.0", "parameter", parameter=0),
                 _instruction(21, "p.1", "parameter", parameter=1),
                 _instruction(22, "p.2", "parameter", parameter=2),
                 _instruction(23, "scatter.84", "scatter", operands=(20, 21, 22))]),
        # the layout loop's body: a dynamic-update-slice of the result
        (4, 42, [_instruction(40, "wide.param", "parameter"),
                 _instruction(41, "get-tuple-element.1", "get-tuple-element",
                              operands=(40,)),
                 _instruction(43, "dynamic-slice.1", "dynamic-slice", operands=(41,)),
                 _instruction(42, "dynamic-update-slice.1", "dynamic-update-slice",
                              operands=(41, 43))]),
        (1, 19, [_instruction(10, "lane", "parameter"),
                 _instruction(11, "indices", "select",
                              "jit(f)/kway.insert/select_n"),
                 _instruction(12, "copy.1", "copy", operands=(10,)),
                 _instruction(13, "fusion.83", "fusion", operands=(12, 11, 11),
                              calls=(2,)),
                 _instruction(14, "constant.1", "constant"),
                 _instruction(15, "tuple.1", "tuple", operands=(14, 13)),
                 _instruction(16, "while.1", "while", operands=(15,), calls=(4,)),
                 _instruction(17, "dus.2", "dynamic-update-slice",
                              "jit(f)/while/body/dynamic_update_slice",
                              operands=(16, 14)),
                 _instruction(18, "copy.2", "copy", operands=(17,)),
                 _instruction(19, "copy.3", "copy", operands=(14,))]),
    ])
    assert trace_scopes.program_phases(proto) == {
        "fusion.83": "insert", "scatter.84": "insert", "copy.1": "insert",
        "tuple.1": "insert", "while.1": "insert", "get-tuple-element.1": "insert",
        "dynamic-slice.1": "insert", "dynamic-update-slice.1": "insert"}


def test_recorded_layout_loops_at_node_scale():
    """At 2^20 sets the v5e lays each scattered lane back out with a
    nameless ``while`` of ``dynamic-update-slice`` ops over the ways, and
    lays each lane out for its scatter with a nameless copy.  They go with
    their scatters: ``kway.hit``'s one loop and ``kway.insert``'s five,
    every nameless copy and loop body op to one of those two phases, none
    to ``kway.resolve``."""
    proto = NODE_HLO.read_bytes()
    ins, _ = trace_scopes._instructions(proto)
    phases = trace_scopes.program_phases(proto)
    loops = [i.name for i in ins.values() if i.opcode == "while" and not i.named]
    assert sorted(phases[n] for n in loops) == ["hit"] + ["insert"] * 5
    moved = [i.name for i in ins.values() if not i.named and i.opcode in (
        "dynamic-update-slice", "dynamic-slice", "copy", "copy-start", "copy-done")
]
    assert len(moved) > 20
    assert {phases.get(n) for n in moved} <= {"hit", "insert", None}
    assert {phases[n] for n in moved if "update-slice" in n} == {"hit", "insert"}
    assert "resolve" not in phases.values()


# ---------------------------------------------------------------------------
# the reduction, on synthetic intervals
# ---------------------------------------------------------------------------

def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              end_ns=float(start + dur))


def _fake():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("window", 1000, 10000),
        _ev("access", 1900, 2200),         # the client's span, not read
        _ev("cache.access", 2000, 2000),
        _ev("cache.access", 7000, 1000),
        _ev("cache.access", 10500, 1500),  # runs past the window's end
    ])])
    ops = [_ev("%while.1", 1500, 4000),          # control flow: not a leaf
           _ev("%fusion.1", 1500, 1000),
           _ev("%sort.2", 3000, 2500),
           _ev("%copy.3", 9000, 500),            # another program
           _ev("%fusion.1", 9100, 300),          # its own op of the same name
           _ev("%fusion.1", 10800, 600)]         # clipped at 11000
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_fn(42)", 1500, 4000),
                                       _ev("jit_other(7)", 9000, 500),
                                       _ev("jit_fn(42)", 10800, 600)]),
        NS(name="XLA Ops", events=ops)])
    meta = {0: {("%while.1", 42): ("jit(fn)/while", "while", "while.1"),
                ("%fusion.1", 42): ("jit(fn)/while/body/kway.probe/add:",
                                    "loop fusion", "fusion.1"),
                ("%sort.2", 42): ("jit(fn)/while/body/kway.resolve/sort", "sort",
                                  "sort.2"),
                ("%copy.3", 7): ("jit(other)/kway.hit/copy", "data formatting",
                                 "copy.3"),
                ("%fusion.1", 7): ("jit(other)/kway.hit/add:", "loop fusion",
                                   "fusion.1")}}
    return NS(planes=[host, dev]), meta


@pytest.mark.parametrize("category,display,leaf", [
    ("while", "while.1", False),            # as a v5e trace gives it
    ("control flow", "cond.3", False),
    ("", "conditional.2", False),
    ("", "call", False),
    ("custom fusion", "fusion.83", True),
    ("sort", "sort.65", True),
])
def test_control_flow_is_not_a_leaf(category, display, leaf):
    assert trace_scopes._is_leaf(("", category, display)) is leaf


def test_split_of_synthetic_intervals():
    pd, meta = _fake()
    s = trace_scopes.reduce(pd, meta, {})
    assert s["window_s"] == pytest.approx(10000e-9)
    # leaf ops of jit_fn's runs only: fusion.1 1000 + 200 (clipped), sort.2
    # 2500; jit_other's fusion.1 is not jit_fn's, whatever its name
    assert s["phase_s"]["probe"] == pytest.approx(1200e-9)
    assert s["phase_s"]["resolve"] == pytest.approx(2500e-9)
    assert s["phase_s"]["hit"] == 0 and s["phase_s"]["other"] == 0
    assert s["leaf_s"] == pytest.approx(3700e-9) and s["scoped"]
    # jit_fn's runs: [1500, 5500] and [10800, 11000] within the window
    assert s["module_s"] == pytest.approx(4200e-9)
    # busy [1500, 5500] + [9000, 9500] + [10800, 11000]: idle
    # [1000,1500] [5500,9000] [9500,10800] = 500 + 3500 + 1300
    assert s["idle_s"] == pytest.approx(5300e-9)
    # by intersection: [2000,4000] lies in busy time (0 idle); [7000,8000]
    # is idle throughout; [10500,11000] overlaps idle [10500,10800]
    assert s["access_idle_s"] == pytest.approx(1300e-9)
    assert s["access_s"] == pytest.approx([2000e-9, 1000e-9, 1500e-9])


def test_a_program_without_scopes_reads_as_unscoped():
    pd, meta = _fake()
    plain = {0: {k: ("jit(fn)/while/body/add",) + v[1:] for k, v in meta[0].items()}}
    s = trace_scopes.reduce(pd, plain, {})
    assert not s["scoped"] and s["phase_s"]["other"] == pytest.approx(3700e-9)


def test_unnamed_op_takes_its_booked_phase():
    """An op without a scope goes to the phase ``booked_scatters`` gave its
    instruction in its own program, else to ``other``."""
    pd, meta = _fake()
    meta[0][("%sort.2", 42)] = ("", "sort", "sort.2")
    s = trace_scopes.reduce(pd, meta, {42: {"sort.2": "insert"}, 7: {"fusion.1": "hit"}})
    assert s["phase_s"]["insert"] == pytest.approx(2500e-9)
    assert s["phase_s"]["hit"] == 0 and s["phase_s"]["other"] == 0


# ---------------------------------------------------------------------------
# the metric readers, on recorded chip traces
# ---------------------------------------------------------------------------

def _ctx(tmp_path, monkeypatch, fixture, cell, attempted, batch=4096):
    from bench import harness
    d = tmp_path / "trace" / cell / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(fixture, d / "host.xplane.pb")
    monkeypatch.setattr(harness, "OUT", tmp_path)
    return NS(trace={"window_s": 1.0}, attempted=attempted,
              cell=NS(name=cell, chips=1, mix={"batch": batch}))


def test_readers_read_nothing_from_a_program_without_the_names(tmp_path,
                                                               monkeypatch):
    """The parent of the scopes: a trace with neither ``kway.*`` scopes nor
    ``cache.access`` spans gives no value, and raises nothing."""
    ctx = _ctx(tmp_path, monkeypatch, TIERED, "x.read_only", 3 * 16 * 4096)
    for phase in trace_scopes.PHASES + ("other",):
        assert trace_scopes.phase_us(ctx, phase) is None
    assert trace_scopes.access_host_us(ctx) is None
    assert trace_scopes.access_idle_pct(ctx) is None
    ctx.trace = None
    assert trace_scopes.phase_us(ctx, "probe") is None


def test_recorded_unnamed_scatters():
    """The replay program's six scatters into state lanes, which the TPU
    compiler leaves without a name: ``kway.hit``'s ``meta_a`` scatter-max
    and ``kway.insert``'s five lane writes."""
    (booked,) = trace_scopes.booked_scatters(SCOPES).values()
    fusions = sorted(p for n, p in booked.items() if n.startswith("fusion."))
    assert fusions == ["hit"] + ["insert"] * 5


def test_recorded_scoped_trace(tmp_path, monkeypatch):
    s = trace_scopes.split(SCOPES)
    ph = s["phase_s"]
    for phase in ("probe", "hit", "victims", "resolve", "insert"):
        assert ph[phase] > 0, phase
    assert ph["scrub"] == 0
    # the leaf ops against the program's own device time, an independent
    # total: they leave out only the gaps between ops (0.6 % here)
    assert s["leaf_s"] == pytest.approx(s["module_s"], rel=0.01)
    assert s["leaf_s"] <= s["module_s"]
    # the five phases hold all but the scan's own bookkeeping
    assert ph["other"] < 0.05 * s["leaf_s"]
    assert len(s["access_s"]) == 20
    assert 0 < s["access_idle_s"] <= s["idle_s"]

    chunks = 3 * 4
    ctx = _ctx(tmp_path, monkeypatch, SCOPES, "x.read_only", chunks * 4096)
    us = {p: trace_scopes.phase_us(ctx, p)
          for p in ("probe", "hit", "victims", "resolve", "insert", "other")}
    assert sum(us.values()) == pytest.approx(s["leaf_s"] / chunks * 1e6, rel=0.01)
    assert trace_scopes.access_host_us(ctx) > 0
    assert 0 < trace_scopes.access_idle_pct(ctx) <= 100


# ---------------------------------------------------------------------------
# recording the fixture (on a TPU)
# ---------------------------------------------------------------------------

def record(out_dir: str) -> None:
    """Trace three replay segments and twenty ``access`` batches of the jnp
    cache at ``getput``'s geometry under a ``window`` span."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.backend import make_backend
    from repro.core.kway import KWayConfig

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("recording needs a TPU")
    cfg = KWayConfig(num_sets=8192, ways=8, seed=20938)
    be = make_backend("jnp", cfg)
    rng = np.random.default_rng(20938)
    b, n = 4096, 4
    segs = [jnp.asarray(rng.integers(0, 2 * cfg.capacity, (n, b), dtype=np.uint32))
            for _ in range(3)]
    en = jnp.ones((n, b), jnp.bool_)
    batches = [rng.integers(0, 2 * cfg.capacity, b, dtype=np.uint32)
               for _ in range(20)]
    state = be.init()
    for _ in range(2):                       # compile every program first
        _, _, state, _ = be.replay(state, segs[0], en)
        k = jax.device_put(batches[0])
        state, *out = be.access(state, k, k.astype(jnp.int32))
        jax.device_get(out)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0          # keeps the file small
    jax.profiler.start_trace(out_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("window"):
        for seg in segs:
            h, e, state, _ = be.replay(state, seg, en)
            jax.device_get((h, e))
        for keys in batches:
            k = jax.device_put(keys)
            state, *out = be.access(state, k, k.astype(jnp.int32))
            jax.device_get(out)
    jax.profiler.stop_trace()
    path = trace_scopes.trace_file(out_dir)
    path.write_bytes(slim(path.read_bytes()))


def _keep(buf, spec: dict) -> bytes:
    """The fields of one message that ``spec`` names: ``{field: None}``
    keeps a field as it is, ``{field: spec}`` keeps a message field cut to
    ``spec``, ``{field: fn}`` keeps ``fn(body)`` unless it is None."""
    out = bytearray()
    for field, value in trace_scopes._fields(buf):
        if field not in spec or value is None:
            continue
        sub = spec[field]
        if isinstance(value, int):
            out += _int(field, value)
        elif sub is None:
            out += _len(field, bytes(value))
        else:
            body = sub(value) if callable(sub) else _keep(value, sub)
            if body is not None:
                out += _len(field, body)
    return bytes(out)


# what booked_scatters reads of an HloProto: computations' ids and roots,
# instructions' name, opcode, op_name, parameter number, id, operands, calls
_HLO = {1: {3: {2: {1: None, 2: None, 7: {2: None}, 9: None, 35: None, 36: None,
                    38: None},
                5: None, 6: None}}}


def _replay_program(entry):
    """An ``event_metadata`` entry of ``/host:metadata``, kept only for the
    replay program, its HLO proto cut to ``_HLO``."""
    md = dict(trace_scopes._fields(entry)).get(2, b"")
    name = next((trace_scopes._text(v) for f, v in trace_scopes._fields(md) if f == 2), "")
    if not trace_scopes.REPLAY_PROGRAM.match(name):
        return None
    return _keep(entry, {1: None, 2: {1: None, 2: None, 5: {
        1: None, 6: lambda proto: _keep(proto, _HLO)}}})


def slim(data: bytes) -> bytes:
    """An XSpace whose ``/host:metadata`` plane keeps only what the
    reduction reads (the other programs' HLO, and the replay's buffer
    assignment, shapes and backend configs, make up most of its size)."""
    def plane(body):
        name = dict(trace_scopes._fields(body)).get(2)
        if name is None or bytes(name) != b"/host:metadata":
            return bytes(body)
        return _keep(body, {1: None, 2: None, 4: _replay_program, 5: None})
    return _keep(memoryview(data), {1: plane, 2: None, 3: None, 4: None})


if __name__ == "__main__":
    record(sys.argv[1])
