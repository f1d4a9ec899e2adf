"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read:

* device planes ``/device:TPU:<n>``: the ``XLA Ops`` line (one event per
  operation run on the chip, named by its HLO instruction, such as
  ``%fusion.9``; a loop's event spans the operations of its body) and the
  ``XLA Modules`` line (one event per program run, such as ``jit_fn(..)``);
* host planes: the harness's own ``TraceAnnotation`` spans: ``window``
  and those its client names (``replay_segment``, ``readback``, ``h2d``,
  ``access`` for the clients so far).

The ``window`` span bounds the measured window; every device interval is
clipped to it.  Busy time is the union of a chip's operation intervals;
idle share is one minus busy over the window.  Each idle gap of the
busiest-idle chip is named by the innermost harness span that covers its
middle, which says what the host was doing while the chip waited.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

SPANS = ("window", "replay_segment", "readback", "h2d", "access")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(trace_dir: Path):
    """The newest ``.xplane.pb`` under ``trace_dir``, parsed."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def summarize(pd, chips: int, spans_read=SPANS) -> dict:
    """Busy time per chip, device time per operation and per program, and
    idle gaps by the host spans named in ``spans_read``, all within the
    ``window`` span (seconds)."""
    spans = []
    devices = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans_read:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    devices = [p for _, p in sorted(devices, key=lambda d: d[0])][:chips]
    if not devices:
        raise ValueError("trace holds no /device:TPU:<n> plane")

    per_chip = []
    for plane in devices:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        per_chip.append((lines.get("XLA Ops", []), lines.get("XLA Modules", [])))
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if windows:
        w0, w1 = windows[0]
    else:
        evs = [ev for ops, _ in per_chip for ev in ops]
        w0 = min(ev.start_ns for ev in evs)
        w1 = max(ev.end_ns for ev in evs)

    def clip(ev):
        return max(ev.start_ns, w0), min(ev.end_ns, w1)

    busy, ops, modules, worst = [], defaultdict(float), defaultdict(float), None
    for op_events, mod_events in per_chip:
        iv = [clip(ev) for ev in op_events]
        iv = [(s, e) for s, e in iv if e > s]
        u = _union(iv)
        b = sum(e - s for s, e in u)
        busy.append(b)
        for ev in op_events:
            s, e = clip(ev)
            if e > s:
                ops[ev.name.split(" = ", 1)[0]] += (e - s) / len(per_chip)
        for ev in mod_events:
            s, e = clip(ev)
            if e > s:
                modules[ev.name] += (e - s) / len(per_chip)
        if worst is None or b < worst[0]:
            worst = (b, u)

    inner = sorted((sp for sp in spans if sp[0] != "window"), key=lambda sp: sp[1])
    starts = [sp[1] for sp in inner]
    longest = max((sp[2] - sp[1] for sp in inner), default=0)
    gaps = defaultdict(float)
    edges = [w0] + [x for iv in worst[1] for x in iv] + [w1]
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        # the spans covering ``mid`` start within ``longest`` before it
        j, cover = bisect.bisect_right(starts, mid), []
        while j > 0 and starts[j - 1] >= mid - longest:
            j -= 1
            if inner[j][2] >= mid:
                cover.append(inner[j])
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else "window"
        gaps[name] += (g1 - g0) * 1e-9

    window = (w1 - w0) * 1e-9
    return {
        "window_s": window,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "busy_by_chip_s": [b * 1e-9 for b in busy],
        "ops_s": {k: v * 1e-9 for k, v in ops.items()},
        "modules_s": {k: v * 1e-9 for k, v in modules.items()},
        "idle_gaps_s": dict(gaps),
    }


def idle_pct(summary: dict) -> float:
    """Idle share of the least busy chip, in percent of the window."""
    return 100.0 * (1.0 - min(summary["busy_by_chip_s"]) / summary["window_s"])


def device_time(summary: dict, pattern: str, *, modules: bool = False) -> float:
    """Device seconds of the operations (or programs) whose name matches
    ``pattern`` (a regular expression, searched)."""
    table = summary["modules_s" if modules else "ops_s"]
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))


def breakdown(summary: dict, top: int = 10) -> dict:
    def head(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": head(summary["ops_s"]),
            "idle_gaps": head(summary["idle_gaps_s"])}
