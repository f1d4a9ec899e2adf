"""Split a traced window by the program's own names: device time by the
``kway.*`` phase scopes of the cache ops, idle time by the program's
``cache.access`` host span.

What it reads, beside what ``trace_reduce`` reads:

* per device operation, its ``tf_op`` (the op's name stack, the
  ``jax.named_scope`` names included, such as
  ``jit(fn)/while/body/closed_call/jit(_access_fused)/kway.probe/add``), its
  ``hlo_category`` and its ``program_id``.  ``jax.profiler.ProfileData``
  gives an event only its name and times, so these come from the XPlane
  file itself: the ``event_metadata`` and ``stat_metadata`` maps of each
  ``/device:TPU:<n>`` plane, decoded by a small protobuf wire-format
  reader (the field numbers of tsl's ``xplane.proto``).  The planes' event
  lines are skipped by their length, so the cost is a few hundred metadata
  records, not one Python step per event;
* the replay program's optimized HLO, from the ``/host:metadata`` plane
  (xla's ``hlo.proto``), for the ops whose name the compiler drops: on a
  TPU a scatter into a 2-D state lane is rebuilt as a 1-D scatter over the
  flattened lane, with no ``op_name``.  The rebuilt index computation
  keeps the name of the scope that built the indices, so such a scatter is
  booked to the nearest scoped op that feeds its indices.  The nameless
  copies that lay the lane out for it and lay its result back (at 2^20
  sets a ``while`` of ``dynamic-update-slice`` ops) go with it;
* the host spans ``window`` (the harness's) and ``cache.access`` (the
  program's, ``repro.core.backend.ACCESS_SPAN``).

Device time is split over the *leaf* operations of the replay program
(``jit_fn``, as ``kway_roofline`` names it) that run inside its ``XLA
Modules`` events: control flow (a ``while``, ``conditional`` or ``call`` op,
by its ``hlo_category`` or its name, whose event spans the ops of its body)
is left out, so nothing is counted twice.  Each leaf op goes to the
innermost ``kway.<phase>`` of its ``tf_op``, or to ``other``.  The
program's own device time (its module events) is kept beside the split as
an independent total.  Idle time is the window less the union of the least
busy chip's operations, and is given to a host span by interval
intersection, not by the middle of the gap.

One parse per trace file serves every metric that reads it.
"""
from __future__ import annotations

import bisect
import collections
import functools
import re
import statistics
from pathlib import Path

from bench import trace_reduce

PHASES = ("scrub", "probe", "hit", "victims", "resolve", "insert")
# named here, not imported from the program: the benchmark also runs
# programs that do not have it, whose traces then read as having none
ACCESS_SPAN = "cache.access"
REPLAY_PROGRAM = re.compile(r"^jit_fn\((\d+)\)$")
_SCOPE = re.compile(r"kway\.(" + "|".join(PHASES) + r")(?=/|:|$)")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_CONTROL = re.compile(r"^[%_]?(while|conditional|call)(\.\d+)?$")
_CONTROL_CATEGORIES = {"while", "conditional", "call", "control flow"}
_METADATA_PLANE = "/host:metadata"

# tsl/profiler/protobuf/xplane.proto field numbers
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVMD_NAME, _EVMD_DISPLAY, _EVMD_STATS = 2, 4, 5
_STMD_NAME = 2
_STAT_MD_ID, _STAT_U64, _STAT_I64, _STAT_STR, _STAT_BYTES, _STAT_REF = 1, 3, 4, 5, 6, 7
# xla/service/hlo.proto field numbers
_HLO_MODULE, _MODULE_COMPUTATIONS = 1, 3
_COMP_INSTRUCTIONS, _COMP_ID, _COMP_ROOT = 2, 5, 6
_INS_NAME, _INS_OPCODE, _INS_METADATA, _INS_PARAMETER = 1, 2, 7, 9
_INS_ID, _INS_OPERANDS, _INS_CALLS = 35, 36, 38
_OP_NAME = 2


# ---------------------------------------------------------------------------
# protobuf wire format: just enough to read XPlane metadata and HLO
# ---------------------------------------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field, None for fixed
    widths (nothing here reads them)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _ints(value) -> list:
    """A repeated integer field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def _map_entry(buf):
    entry = dict(_fields(buf))
    return entry.get(_MAP_KEY, 0), entry.get(_MAP_VALUE, b"")


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _planes(buf):
    """(name, plane) of each plane of an XSpace."""
    for field, plane in _fields(buf):
        if field == _SPACE_PLANES:
            yield next((_text(v) for f, v in _fields(plane) if f == _PLANE_NAME), ""), plane


def _event_metadata(plane):
    """(id, name, display name, {stat name: value}) of each event metadata
    entry of a plane."""
    stat_names, event_mds = {}, []
    for field, value in _fields(plane):
        if field == _PLANE_STAT_MD:
            key, md = _map_entry(value)
            stat_names[key] = next(
                (_text(v) for f, v in _fields(md) if f == _STMD_NAME), "")
        elif field == _PLANE_EVENT_MD:
            event_mds.append(_map_entry(value))
    for eid, md in event_mds:
        name = display = ""
        stats = {}
        for f, v in _fields(md):
            if f == _EVMD_NAME:
                name = _text(v)
            elif f == _EVMD_DISPLAY:
                display = _text(v)
            elif f == _EVMD_STATS:
                stat = dict(_fields(v))
                key = stat_names.get(stat.get(_STAT_MD_ID))
                if _STAT_STR in stat:
                    stats[key] = _text(stat[_STAT_STR])
                elif _STAT_REF in stat:
                    stats[key] = stat_names.get(stat[_STAT_REF], "")
                elif _STAT_BYTES in stat:
                    stats[key] = stat[_STAT_BYTES]
                else:
                    stats[key] = stat.get(_STAT_U64, stat.get(_STAT_I64))
        yield eid, name, display, stats


def op_metadata(path) -> dict:
    """Device plane index -> {(event name, program_id) -> (tf_op,
    hlo_category, display name)} of an ``.xplane.pb`` file.  An event name
    (the op's HLO text) may recur in several programs, hence the key."""
    out = {}
    for name, plane in _planes(memoryview(Path(path).read_bytes())):
        m = _DEVICE.match(name)
        if m:
            out[int(m.group(1))] = {
                (ev, stats.get("program_id")): (stats.get("tf_op", ""),
                                                stats.get("hlo_category", ""), display)
                for _, ev, display, stats in _event_metadata(plane)}
    return out


# ---------------------------------------------------------------------------
# scatters the compiler left without a name
# ---------------------------------------------------------------------------

_Instruction = collections.namedtuple(
    "_Instruction", "name opcode phase named operands calls parameter computation")


def _instructions(proto) -> tuple:
    """id -> ``_Instruction`` of every instruction of an ``HloProto``, and
    computation id -> its root instruction's id."""
    instructions, roots = {}, {}
    module = next(v for f, v in _fields(proto) if f == _HLO_MODULE)
    for f, comp in _fields(module):
        if f != _MODULE_COMPUTATIONS:
            continue
        fields = list(_fields(comp))
        cid = next((v for g, v in fields if g == _COMP_ID), 0)
        roots[cid] = next((v for g, v in fields if g == _COMP_ROOT), None)
        for g, ins in fields:
            if g != _COMP_INSTRUCTIONS:
                continue
            d = {_INS_NAME: b"", _INS_OPCODE: b"", _INS_PARAMETER: 0, _INS_ID: 0}
            operands, calls, op_name = [], [], ""
            for h, v in _fields(ins):
                if h == _INS_OPERANDS:
                    operands += _ints(v)
                elif h == _INS_CALLS:
                    calls += _ints(v)
                elif h == _INS_METADATA:
                    op_name = next((_text(x) for k, x in _fields(v) if k == _OP_NAME), "")
                else:
                    d[h] = v
            found = _SCOPE.findall(op_name)
            instructions[d[_INS_ID]] = _Instruction(
                _text(d[_INS_NAME]), _text(d[_INS_OPCODE]),
                found[-1] if found else None, bool(op_name), operands, calls,
                d[_INS_PARAMETER], cid)
    return instructions, roots


def scatter_phases(proto) -> dict:
    """Instruction name -> phase, for each op of an ``HloProto`` whose
    result is a scatter that carries no ``kway.*`` scope (a fusion whose
    root is one, or the scatter itself): the phase of the nearest scoped op
    that feeds the scatter's indices, followed through fusion parameters to
    the fusion's operands.  An op with no scoped feeder is not listed."""
    ins, roots = _instructions(proto)
    return {ins[i].name: p for i, p in _scatters(ins, roots).items()}


def _scatters(ins: dict, roots: dict) -> dict:
    """``scatter_phases`` by instruction id."""
    caller = {c: i for i in ins.values() if i.opcode == "fusion" for c in i.calls}

    def feeders(i):
        if i.opcode == "parameter":
            f = caller.get(i.computation)
            return [f.operands[i.parameter]] if f and i.parameter < len(f.operands) else []
        if i.opcode == "fusion":
            return [roots.get(c) for c in i.calls[:1]]
        return i.operands

    def indices_phase(scatter):
        # a scatter's operands: N inputs, the indices, N updates
        start = scatter.operands[(len(scatter.operands) - 1) // 2]
        seen, queue = {start}, collections.deque([start])
        while queue:
            i = ins.get(queue.popleft())
            if i is None:
                continue
            if i.phase:
                return i.phase
            for n in feeders(i):
                if n not in seen:
                    seen.add(n)
                    queue.append(n)
        return None

    out = {}
    for iid, i in ins.items():
        result = ins.get(roots.get(i.calls[0])) if i.opcode == "fusion" and i.calls else i
        if (i.phase is None and result is not None and result.opcode == "scatter"
                and result.phase is None and result.operands):
            phase = indices_phase(result)
            if phase:
                out[iid] = phase
    return out


# ops that join nothing: a constant or a parameter may be shared by ops of
# any phase, and runs no device time of its own
_NO_BRIDGE = {"constant", "parameter"}
_LOOPS = {"while", "call", "conditional"}


def _attached(ins: dict, scatters: dict) -> dict:
    """Instruction id -> phase of each op with no name at all (no
    ``op_name``) that data flow ties to a booked scatter through other such
    ops: the copies that lay a state lane out for the scatter, and the
    nameless loop that lays its result back out (a large lane's layout
    change becomes a ``while`` of ``dynamic-update-slice`` ops), with the
    ops of that loop's body.  Each goes to the nearest booked scatter, by
    the fewest such edges; a named op, a constant and a parameter join
    nothing."""
    users = collections.defaultdict(list)
    members = collections.defaultdict(list)
    loop_of = {}
    for iid, i in ins.items():
        for o in i.operands:
            users[o].append(iid)
        members[i.computation].append(iid)
        if i.opcode in _LOOPS and not i.named:
            for c in i.calls:
                loop_of[c] = iid

    def neighbours(iid):
        i = ins[iid]
        out = i.operands + users[iid]
        if i.opcode in _LOOPS:
            out = out + [m for c in i.calls for m in members[c]]
        if i.computation in loop_of:
            out = out + [loop_of[i.computation]]
        return out

    phase = {}
    queue = collections.deque(scatters.items())
    while queue:
        iid, p = queue.popleft()
        for n in neighbours(iid):
            i = ins.get(n)
            if (i is None or n in phase or n in scatters or i.named
                    or i.opcode in _NO_BRIDGE):
                continue
            phase[n] = p
            queue.append((n, p))
    return phase


def program_phases(proto) -> dict:
    """Instruction name -> phase of every op of an ``HloProto`` that runs
    with no ``kway.*`` scope of its own yet belongs to a phase: the
    nameless scatters (``scatter_phases``) and the nameless ops that lay
    their lanes out (``_attached``)."""
    ins, roots = _instructions(proto)
    scatters = _scatters(ins, roots)
    return {ins[i].name: p for i, p in {**_attached(ins, scatters), **scatters}.items()}


def booked_scatters(path) -> dict:
    """program_id -> ``program_phases`` of each replay program in the
    ``/host:metadata`` plane of an ``.xplane.pb`` file (empty where the
    plane is missing)."""
    out = {}
    for name, plane in _planes(memoryview(Path(path).read_bytes())):
        if name != _METADATA_PLANE:
            continue
        for pid, program, _, stats in _event_metadata(plane):
            proto = next((v for v in stats.values() if isinstance(v, memoryview)), None)
            if REPLAY_PROGRAM.match(program) and proto is not None:
                out[pid] = program_phases(proto)
    return out


# ---------------------------------------------------------------------------
# the split
# ---------------------------------------------------------------------------

def phase_of(tf_op: str) -> str:
    """The innermost ``kway.<phase>`` scope of an op's name stack, or
    ``other``."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else "other"


def _is_leaf(meta) -> bool:
    _, category, display = meta
    return category not in _CONTROL_CATEGORIES and not _CONTROL.match(display)


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def split(path, chips: int = 1) -> dict:
    """The scope split of one trace file (seconds), within its ``window``
    span, on the first ``chips`` TPU planes:

    ``phase_s``   leaf-op device time of the replay program per phase
                  (``PHASES`` and ``other``), averaged over the chips;
    ``leaf_s``    their sum; ``scoped``: whether any op carries a
                  ``kway.*`` scope;
    ``module_s``  the replay program's own device time (its ``XLA Modules``
                  events), the total ``leaf_s`` is checked against;
    ``idle_s``    idle time of the least busy chip;
    ``access_s``  durations of the ``cache.access`` spans that start in the
                  window; ``access_idle_s``: the idle time inside them.
    """
    path = Path(path)
    st = path.stat()
    return _split(str(path), st.st_mtime_ns, st.st_size, chips)


@functools.lru_cache(maxsize=4)
def _split(path: str, _mtime: int, _size: int, chips: int) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), op_metadata(path),
                  booked_scatters(path), chips)


def reduce(pd, meta: dict, booked: dict, chips: int = 1) -> dict:
    """``split`` of a parsed trace ``pd`` (``ProfileData``'s planes, lines
    and events) with its device ops' metadata ``meta`` (``op_metadata``)
    and its unnamed scatters' phases ``booked`` (``booked_scatters``)."""
    spans, devices = [], []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("window", ACCESS_SPAN):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    devices = sorted(devices, key=lambda d: d[0])[:chips]
    if not devices:
        raise ValueError("trace holds no /device:TPU:<n> plane")

    per_chip = []
    for idx, plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = list(lines["XLA Ops"].events) if "XLA Ops" in lines else []
        mods = list(lines["XLA Modules"].events) if "XLA Modules" in lines else []
        per_chip.append((meta.get(idx, {}), ops, mods))

    # the window rule of trace_reduce.summarize: the first ``window`` span,
    # else the extent of the operations
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if windows:
        w0, w1 = windows[0]
    else:
        evs = [ev for _, ops, _ in per_chip for ev in ops]
        w0 = min(ev.start_ns for ev in evs)
        w1 = max(ev.end_ns for ev in evs)

    phase_ns = dict.fromkeys(PHASES + ("other",), 0.0)
    module_ns = 0.0
    worst = None
    for ops_meta, ops, mods in per_chip:
        # the replay program's runs: an op belongs to the run it starts in
        runs = sorted((ev.start_ns, ev.end_ns, int(m.group(1))) for ev in mods
                      for m in [REPLAY_PROGRAM.match(ev.name)] if m)
        starts = [r[0] for r in runs]
        module_ns += sum(max(0.0, min(e, w1) - max(s, w0))
                         for s, e, _ in runs) / len(per_chip)
        # (event name, program) -> its phase, or None when it is not a leaf
        # op: decided once per name, not once per event
        phase = {}
        busy = []
        for ev in ops:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            busy.append((s, e))
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            if k < 0 or ev.start_ns >= runs[k][1]:
                continue
            key = (ev.name, runs[k][2])
            if key not in phase:
                m = ops_meta.get(key)
                phase[key] = None
                if m and _is_leaf(m):
                    phase[key] = phase_of(m[0])
                    if phase[key] == "other":
                        phase[key] = booked.get(key[1], {}).get(m[2], "other")
            if phase[key] is not None:
                phase_ns[phase[key]] += (e - s) / len(per_chip)
        u = trace_reduce._union(busy)
        b = sum(e - s for s, e in u)
        if worst is None or b < worst[0]:
            worst = (b, u)

    edges = [w0] + [x for iv in worst[1] for x in iv] + [w1]
    idle = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
    access = [(s, e) for n, s, e in spans if n == ACCESS_SPAN and w0 <= s < w1]
    access_union = trace_reduce._union([(s, min(e, w1)) for s, e in access])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "phase_s": {k: v * 1e-9 for k, v in phase_ns.items()},
        "leaf_s": sum(phase_ns.values()) * 1e-9,
        "module_s": module_ns * 1e-9,
        "scoped": any(phase_ns[p] > 0 for p in PHASES),
        "idle_s": sum(g1 - g0 for g0, g1 in idle) * 1e-9,
        "access_s": [(e - s) * 1e-9 for s, e in access],
        "access_idle_s": _overlap(idle, access_union) * 1e-9,
    }


# ---------------------------------------------------------------------------
# what the metric readers call
# ---------------------------------------------------------------------------

def trace_file(trace_dir) -> Path | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, the file
    ``trace_reduce.load`` reads, or None."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def of_run(ctx) -> dict | None:
    """The split of a traced run's window (``ctx`` as the harness gives it
    to a metric reader), or None for an untraced run."""
    if not ctx.trace:
        return None
    from bench import harness
    path = trace_file(harness.OUT / "trace" / ctx.cell.name)
    return split(path, ctx.cell.chips) if path else None


def phase_us(ctx, phase: str) -> float | None:
    """Device microseconds per chunk of ``batch`` requests under the
    ``kway.<phase>`` scope (``other``: under none) of the replay program;
    None where no op carries a ``kway.*`` scope."""
    s = of_run(ctx)
    if not s or not s["scoped"] or not ctx.attempted:
        return None
    chunks = ctx.attempted / int(ctx.cell.mix["batch"])
    return s["phase_s"][phase] / chunks * 1e6


def access_host_us(ctx) -> float | None:
    """Median duration of the window's ``cache.access`` spans (µs)."""
    s = of_run(ctx)
    if not s or not s["access_s"]:
        return None
    return statistics.median(s["access_s"]) * 1e6


def access_idle_pct(ctx) -> float | None:
    """Device-idle time inside ``cache.access`` spans over all device-idle
    time of the window (%)."""
    s = of_run(ctx)
    if not s or not s["access_s"] or s["idle_s"] <= 0:
        return None
    return 100.0 * s["access_idle_s"] / s["idle_s"]
