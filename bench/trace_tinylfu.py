"""Split a traced replay window by the admission step's own names: device
time under the ``tinylfu.record`` scope (``admission.record``: doorkeeper,
counters, aging) and the ``tinylfu.admit`` scope (the victim peek, the two
estimates and the comparison, ``CacheBackend.access_admitted``).

It reads the trace as ``bench/trace_scopes.py`` does and reuses its
parsing: each leaf operation of the replay program (``jit_fn``) that runs
inside its ``XLA Modules`` events and the harness's ``window`` span, with
its ``tf_op`` from the device plane's metadata and the program's optimized
HLO from the ``/host:metadata`` plane.  An op is booked to the *outermost*
``tinylfu.*`` scope of its ``tf_op``: the peek runs the cache's own probe
and victim order inside ``tinylfu.admit``, so those ops count here and,
by their innermost ``kway.*`` phase, in ``kway_us.probe`` and
``kway_us.victims`` as well.  The ops the compiler leaves without a name
(on a TPU the counters' scatter-max into the ``[4, width / 8]`` sketch is
rebuilt as a 1-D scatter with no ``op_name``, as the cache's lane scatters
are) are booked by ``trace_scopes``' data-flow rules, with each ``kway.*``
phase kept as a scope of its own, so that a cache lane's scatter or copy is
never taken for the sketch's.

One parse per trace file serves every metric that reads it.
"""
from __future__ import annotations

import bisect
import functools
import re
from pathlib import Path

from bench import trace_scopes as ts

SCOPES = ("record", "admit")
_TINYLFU = re.compile(r"tinylfu\.(" + "|".join(SCOPES) + r")(?=/|:|$)")


def scope_of(name: str) -> str | None:
    """The outermost ``tinylfu.*`` scope of an op's name stack, else its
    innermost ``kway.*`` phase as ``kway.<phase>``, else None."""
    found = _TINYLFU.search(name or "")
    if found:
        return found.group(1)
    phases = ts._SCOPE.findall(name or "")
    return f"kway.{phases[-1]}" if phases else None


def _instructions(proto) -> tuple:
    """``trace_scopes._instructions`` with ``scope_of`` for an op's phase."""
    instructions, roots = {}, {}
    module = next(v for f, v in ts._fields(proto) if f == ts._HLO_MODULE)
    for f, comp in ts._fields(module):
        if f != ts._MODULE_COMPUTATIONS:
            continue
        fields = list(ts._fields(comp))
        cid = next((v for g, v in fields if g == ts._COMP_ID), 0)
        roots[cid] = next((v for g, v in fields if g == ts._COMP_ROOT), None)
        for g, ins in fields:
            if g != ts._COMP_INSTRUCTIONS:
                continue
            d = {ts._INS_NAME: b"", ts._INS_OPCODE: b"", ts._INS_PARAMETER: 0, ts._INS_ID: 0}
            operands, calls, op_name = [], [], ""
            for h, v in ts._fields(ins):
                if h == ts._INS_OPERANDS:
                    operands += ts._ints(v)
                elif h == ts._INS_CALLS:
                    calls += ts._ints(v)
                elif h == ts._INS_METADATA:
                    op_name = next((ts._text(x) for k, x in ts._fields(v)
                                    if k == ts._OP_NAME), "")
                else:
                    d[h] = v
            instructions[d[ts._INS_ID]] = ts._Instruction(
                ts._text(d[ts._INS_NAME]), ts._text(d[ts._INS_OPCODE]),
                scope_of(op_name), bool(op_name), operands, calls,
                d[ts._INS_PARAMETER], cid)
    return instructions, roots


def program_scopes(proto) -> dict:
    """Instruction name -> ``scope_of`` of every op of an ``HloProto`` that
    carries no scope of its own yet is booked to one by data flow: the
    nameless scatters and the nameless ops tied to them."""
    ins, roots = _instructions(proto)
    scatters = ts._scatters(ins, roots)
    return {ins[i].name: p for i, p in {**ts._attached(ins, scatters), **scatters}.items()}


def booked(path) -> dict:
    """program_id -> ``program_scopes`` of each replay program in the
    ``/host:metadata`` plane of an ``.xplane.pb`` file."""
    out = {}
    for name, plane in ts._planes(memoryview(Path(path).read_bytes())):
        if name != ts._METADATA_PLANE:
            continue
        for pid, program, _, stats in ts._event_metadata(plane):
            proto = next((v for v in stats.values() if isinstance(v, memoryview)), None)
            if ts.REPLAY_PROGRAM.match(program) and proto is not None:
                out[pid] = program_scopes(proto)
    return out


def reduce(pd, meta: dict, scopes: dict, chips: int = 1) -> dict:
    """Seconds of the replay program's leaf ops within the ``window`` span
    under each of ``SCOPES``, averaged over the first ``chips`` TPU planes,
    of a parsed trace ``pd`` with its ops' metadata ``meta``
    (``trace_scopes.op_metadata``) and its nameless ops' scopes ``scopes``
    (``booked``)."""
    windows = [(ev.start_ns, ev.end_ns) for plane in pd.planes
               if plane.name.startswith("/host:") for line in plane.lines
               for ev in line.events if ev.name == "window"]
    devices = sorted((int(m.group(1)), plane) for plane in pd.planes
                     for m in [ts._DEVICE.match(plane.name)] if m)[:chips]
    if not devices:
        raise ValueError("trace holds no /device:TPU:<n> plane")
    w0, w1 = windows[0] if windows else (float("-inf"), float("inf"))
    out = dict.fromkeys(SCOPES, 0.0)
    for idx, plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines["XLA Ops"].events if "XLA Ops" in lines else []
        mods = lines["XLA Modules"].events if "XLA Modules" in lines else []
        runs = sorted((ev.start_ns, ev.end_ns, int(m.group(1))) for ev in mods
                      for m in [ts.REPLAY_PROGRAM.match(ev.name)] if m)
        starts = [r[0] for r in runs]
        ops_meta, scope = meta.get(idx, {}), {}
        for ev in ops:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            if e <= s or k < 0 or ev.start_ns >= runs[k][1]:
                continue
            key = (ev.name, runs[k][2])
            if key not in scope:
                m = ops_meta.get(key)
                scope[key] = None
                if m and ts._is_leaf(m):
                    scope[key] = scope_of(m[0]) or scopes.get(key[1], {}).get(m[2])
            if scope[key] in out:
                out[scope[key]] += (e - s) * 1e-9 / len(devices)
    return out


@functools.lru_cache(maxsize=4)
def _split(path: str, _mtime: int, _size: int, chips: int) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), ts.op_metadata(path), booked(path), chips)


def scope_us(ctx, scope: str) -> float | None:
    """Device microseconds per chunk of ``batch`` requests under the
    ``tinylfu.<scope>`` scope of the replay program; None for an untraced
    run, or where no op carries that scope (a program without admission,
    or without these names)."""
    if not ctx.trace or not ctx.attempted:
        return None
    from bench import harness
    path = ts.trace_file(harness.OUT / "trace" / ctx.cell.name)
    if path is None:
        return None
    st = path.stat()
    s = _split(str(path), st.st_mtime_ns, st.st_size, ctx.cell.chips)[scope]
    if s <= 0:
        return None
    return s / (ctx.attempted / int(ctx.cell.mix["batch"])) * 1e6
