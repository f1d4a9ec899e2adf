"""Compile every Pallas kernel of the main path for a TPU v5e, with Mosaic
(``interpret=False``) and no chip attached.

Interpret mode accepts kernels the TPU compiler refuses (misaligned blocks,
scalar stores to VMEM, ops Mosaic cannot lower, more VMEM than the scoped
limit).  These tests hand each kernel to the installed TPU compiler at
deployment shapes, so such a kernel fails here instead of on the chip.  The
VMEM checks of the pallas backend are compiled at their largest admitted
size, which ties ``RESIDENT_VMEM_BUDGET`` and ``VMEM_LIMIT`` to what
the compiler accepts.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hierarchy as H
from repro.core.admission import TinyLFUState, for_capacity
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig, KWayState
from repro.core.policies import Policy
from repro.kernels import kway_probe as kp
from repro.kernels import replay as rp
from repro.kernels.paged_attention import paged_attention

WAYS, B, T = 8, 256, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


def _lanes(sds, sets, ways):
    """(keys, fprint, vals, meta_a, meta_b) shapes of one cache tier."""
    return ([sds((sets, ways), jnp.uint32)] * 2
            + [sds((sets, ways))] * 3)


@pytest.mark.parametrize("policy", list(Policy))
def test_kway_probe_compiles(sds, policy):
    s = 2048
    _compile(lambda *a: kp.kway_probe(*a, policy=int(policy), ways=WAYS,
                                      qt=128, interpret=False,
                                      full_order=True),
             *[sds((s, kp.LANES))] * 4, *[sds((B,))] * 3)


def test_kway_fused_probe_compiles_at_probe_vmem_limit(sds):
    """The fused probe at the largest cache ``VMEM_LIMIT`` admits —
    the pallas backend refuses anything larger (``probe_fits``)."""
    per_set = kp.probe_vmem_bytes(1) - kp.probe_vmem_bytes(0)
    s = (kp.VMEM_LIMIT - kp.probe_vmem_bytes(0)) // per_set
    for policy in Policy:
        _compile(lambda *a: kp.kway_fused_probe(
            *a, policy=int(policy), ways=WAYS, qt=128, interpret=False),
            *[sds((s, kp.LANES))] * 4, *[sds((B,))] * 5)


@pytest.mark.parametrize("policy,tinylfu,ttl", [
    (Policy.LRU, False, False), (Policy.LFU, True, False),
    (Policy.RANDOM, False, False), (Policy.HYPERBOLIC, False, False),
    (Policy.LRU, False, True)], ids=["lru", "lfu-tinylfu", "random",
                                     "hyperbolic", "lru-ttl"])
def test_replay_resident_compiles_at_budget(sds, policy, tinylfu, ttl):
    """The flat megakernel at the largest power-of-two cache that
    ``RESIDENT_VMEM_BUDGET`` keeps on the resident path."""
    sets = 1
    while make_backend("pallas", KWayConfig(num_sets=2 * sets, ways=WAYS)
                       ).resident_fits():
        sets *= 2
    assert sets >= 2048
    tl = for_capacity(sets * WAYS) if tinylfu else None
    args = _lanes(sds, sets, WAYS) + [sds(()), sds((T, B), jnp.uint32),
                                      sds((T, B), jnp.bool_)]
    if ttl:
        args += [sds((sets, WAYS)), sds((T, B))]

    def run(*a):
        kw = dict(expiry=a[8], ttls=a[9]) if ttl else {}
        return rp.replay_resident(*a[:8], policy=int(policy), ways=WAYS,
                                  num_sets=sets, seed=0, tinylfu=tl,
                                  interpret=False, **kw)[:2]
    _compile(run, *args)


@pytest.mark.parametrize("ttl", [False, True], ids=["plain", "ttl"])
def test_replay_hierarchical_compiles(sds, ttl):
    """The hierarchy kernel, L1 64x16 in VMEM over a 2^18-set L2 in HBM:
    this lowers the L2 row-block DMA branch interpret mode never takes."""
    hier = H.HierarchyConfig(l1_sets=64, l1_ways=16)
    l2_sets = 1 << 18
    args = (_lanes(sds, hier.l1_sets, hier.l1_ways)
            + _lanes(sds, l2_sets, WAYS)
            + [sds(()), sds((T, B), jnp.uint32), sds((T, B), jnp.bool_)])
    if ttl:
        args += [sds((hier.l1_sets, hier.l1_ways)), sds((l2_sets, WAYS)),
                 sds((T, B))]

    def run(*a):
        kw = dict(l1_exp=a[13], l2_exp=a[14], ttls=a[15]) if ttl else {}
        return rp.replay_hierarchical(
            *a[:13], policy=int(Policy.LRU), l1_ways=hier.l1_ways,
            l2_ways=WAYS, l1_sets=hier.l1_sets, l2_sets=l2_sets, seed=0,
            interpret=False, **kw)[:2]
    _compile(run, *args)


def test_paged_attention_compiles(sds):
    """Decode attention at minicpm-2b widths (36 heads, head_dim 64,
    bf16 page pool of 16-token pages)."""
    b, heads, d, page, pages, pps = 8, 36, 64, 16, 256, 16
    _compile(lambda *a: paged_attention(*a, interpret=False),
             sds((b, heads, d), jnp.bfloat16),
             sds((heads, pages, page, d), jnp.bfloat16),
             sds((heads, pages, page, d), jnp.bfloat16),
             sds((b, pps)), sds((b,)))


def _shapes(line: str) -> list[str]:
    """The array shapes, with their layouts, of an instruction's result (a
    tuple's in order), memory spaces dropped."""
    result = line.split(" = ", 1)[1].rsplit("(%", 1)[0]
    return [re.sub(r"S\(\d+\)", "", m)
            for m in re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", result)]


def _replay_hlo(sds, sets, ways=8, steps=64, b=4096):
    """A ``steps``-chunk scan over ``JnpBackend.access`` at ``sets`` x
    ``ways``, compiled for a v5e: the program's text."""
    be = make_backend("jnp", KWayConfig(num_sets=sets, ways=ways))

    def replay(state, chunks, enabled):
        def step(st, xs):
            st, hit, _, _, ev = be.access(st, xs[0], xs[0].astype(jnp.int32),
                                          None, xs[1])
            return st, (jnp.sum(hit), jnp.sum(ev))
        return jax.lax.scan(step, state, (chunks, enabled))

    state = KWayState(*_lanes(sds, sets, ways), clock=sds(()))
    return jax.jit(replay).lower(
        state, sds((steps, b), jnp.uint32), sds((steps, b), jnp.bool_)
    ).compile().as_text()


def _lane_sized(line: str, slots: int) -> bool:
    return slots in [math.prod(int(d) for d in dims.split(",") if d)
                     for dims in re.findall(r"\[([\d,]*)\]",
                                            " ".join(_shapes(line)))]


def _computations(hlo: str):
    """({computation: its instruction lines}, the entry's name)."""
    comps, cur, entry = {}, None, None
    for ln in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) ", ln)
        if head and ln.rstrip().endswith("{"):
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
        elif ln.startswith("}"):
            cur = None
        elif cur is not None and " = " in ln:
            cur.append(ln)
    return comps, entry


def _body(loop: str) -> str:
    return re.search(r"body=%([\w.\-]+)", loop).group(1)


def test_jnp_replay_writes_lanes_in_place(sds):
    """A 64-chunk scan over ``JnpBackend.access`` at 2^20 sets x 8 ways, the
    node-scale geometry: every lane scatter goes through the lanes' slot
    view, in the order of the lanes' tiles, so the program's loops are the
    scan and, in its body, the two write guards (``kway._scatter_when``),
    no ``dynamic-update-slice`` lays out a lane, and no copy changes a
    lane's layout (the view is a bitcast)."""
    sets, ways = 1 << 20, 8
    hlo = _replay_hlo(sds, sets, ways)

    comps, entry = _computations(hlo)
    (scan,) = [ln for ln in comps[entry] if " while(" in ln]
    guards = [ln for ln in comps[_body(scan)] if " while(" in ln]
    assert len(guards) == 2, len(guards)
    loops = re.findall(r"\bwhile\(", hlo)
    assert len(loops) == 3, len(loops)

    def lane_sized(line):
        return _lane_sized(line, sets * ways)

    assert not [ln for ln in hlo.splitlines()
                if "dynamic-update-slice(" in ln and lane_sized(ln)]
    lines = [ln for ln in hlo.splitlines() if " = " in ln]
    shapes = {ln.split(" = ", 1)[0].split()[-1]: _shapes(ln) for ln in lines}
    for ln in lines:
        op = re.search(r" (copy|copy-start)\(%([\w.\-]+)\)", ln)
        if op and lane_sized(ln):
            if op.group(1) == "copy":
                dst, src = _shapes(ln)[0], shapes["%" + op.group(2)][0]
            else:                       # (destination, source, context)
                dst, src = _shapes(ln)[:2]
            assert dst == src, ln


#: Lane-sized copies in the body of the unguarded scan (compiled for a
#: v5e): at 8192 sets the two row-gather layout copies of
#: ``keys`` and ``fprint`` and the ``meta_a`` transpose; none at 2^20.  The
#: guarded body adds at 8192 sets four 256 KB copies between HBM and ``S(1)``
#: around the insert guard (``vals`` and ``meta_b`` in and out, which
#: memory-space assignment keeps in HBM across the scan there), and none at
#: 2^20, where a lane is 32 MB.
GUARDED_BODY_COPIES = {8192: 3 + 4, 1 << 20: 0}


@pytest.mark.parametrize("sets", sorted(GUARDED_BODY_COPIES))
def test_jnp_replay_guards_lane_scatters(sds, sets):
    """The scan's body holds the two write guards, loops of zero or one
    trips (``kway._scatter_when``): hit's under ``kway.hit`` with the
    ``meta_a`` scatter-max, insert's under ``kway.insert`` with the five
    lane scatters, and no lane scatter outside them.  No lane-sized
    ``dynamic-update-slice`` appears, neither guard copies a lane, and the
    body copies no more lanes than ``GUARDED_BODY_COPIES`` allows."""
    ways = 8
    hlo = _replay_hlo(sds, sets, ways)
    slots = sets * ways
    comps, entry = _computations(hlo)
    (scan,) = [ln for ln in comps[entry] if " while(" in ln]
    body = _body(scan)
    guards = {re.search(r"kway\.(hit|insert)/while", ln).group(1): _body(ln)
              for ln in comps[body] if " while(" in ln}
    assert sorted(guards) == ["hit", "insert"]

    def lane_ops(comp, pattern):
        return [ln for ln in comps[comp]
                if _lane_sized(ln, slots) and re.search(pattern, ln)]

    scatter = r'op_name="[^"]*/scatter(-max|-add)?"'
    assert len(lane_ops(guards["hit"], scatter)) == 1
    assert len(lane_ops(guards["insert"], scatter)) == 5
    assert not lane_ops(body, scatter)
    assert not [ln for ln in hlo.splitlines()
                if "dynamic-update-slice(" in ln and _lane_sized(ln, slots)]
    copy = r" (copy|copy-start)\("
    assert not lane_ops(guards["hit"], copy)
    assert not lane_ops(guards["insert"], copy)
    assert len(lane_ops(body, copy)) <= GUARDED_BODY_COPIES[sets]


def _replay_program(sds, sets, tinylfu, steps=64, b=4096):
    """``JnpBackend.replay``'s own scan (the program ``jit_fn``) at ``sets``
    x 8 ways, with or without TinyLFU admission, compiled for a v5e."""
    ways = 8
    be = make_backend("jnp", KWayConfig(num_sets=sets, ways=ways))
    state = KWayState(*_lanes(sds, sets, ways), clock=sds(()))
    if tinylfu is None:
        sketch = sds(())
    else:
        sketch = TinyLFUState(packed=sds((4, tinylfu.width // 8), jnp.uint32),
                              door=sds((tinylfu.door_bits // 32,), jnp.uint32),
                              additions=sds(()))
    args = (state, sds((steps, b), jnp.uint32), sds((steps, b), jnp.bool_))
    jax.eval_shape(lambda st, ch, en, sk: be.replay(st, ch, en, tinylfu=tinylfu,
                                                    sketch=sk),
                   *args, sketch)                      # builds the scan
    fn = be._replay_fns[(tinylfu, False, False)]
    return fn, fn.lower(*args, sketch, None).compile()


def _carried_in_s1(hlo: str, sets: int) -> list:
    """Which of the scan's ``[sets, 8]`` carried lanes lie in ``S(1)``, in
    carry order."""
    comps, entry = _computations(hlo)
    (scan,) = [ln for ln in comps[entry] if " while(" in ln]
    carry = scan.split(" = ", 1)[1].split(" while(", 1)[0]
    return ["S(1)" in s for s in re.findall(rf"\w+\[{sets},8\]\{{[^}}]*\}}", carry)]


def test_jnp_tinylfu_replay_compiles_with_its_names(sds):
    """The 64-chunk replay behind TinyLFU admission at 2^17 sets x 8 ways
    with the sketch ``for_capacity`` sizes (``ycsbc_tinylfu``'s geometry)
    compiles for a v5e.  The doorkeeper's scatter keeps its
    ``tinylfu.record`` name; the counters' scatter-max, which the compiler
    rebuilds as a nameless 1-D scatter, is booked to ``record`` by data
    flow (``bench/trace_tinylfu.py``); ops under ``tinylfu.admit`` are
    there.  The aging ``cond`` moves none of the cache's lanes out of
    ``S(1)``: the scan carries them where the scan without admission
    does."""
    from bench import trace_tinylfu
    sets = 1 << 17
    _, compiled = _replay_program(sds, sets, for_capacity(sets * 8))
    hlo = compiled.as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    scatters = [ln for ln in hlo.splitlines() if re.search(r" scatter(-max)?\(", ln)]
    assert any("tinylfu.record/scatter" in ln for ln in scatters)
    assert any("tinylfu.admit" in n for n in names)
    assert any("tinylfu.record/cond" in ln for ln in hlo.splitlines()
               if " conditional(" in ln)

    (module,) = compiled.runtime_executable().hlo_modules()
    body = module.as_serialized_hlo_module_proto()
    proto = bytes([1 << 3 | 2]) + _varint(len(body)) + body     # HloProto.hlo_module
    booked = trace_tinylfu.program_scopes(memoryview(proto))
    packed = [ln.split(" = ")[0].split()[-1].lstrip("%") for ln in scatters
              if "u32[524288]" in ln.split(" = ", 1)[1].split(" scatter", 1)[0]]
    assert packed and all(booked.get(n) == "record" for n in packed), packed

    _, plain = _replay_program(sds, sets, None)
    assert (_carried_in_s1(hlo, sets) == _carried_in_s1(plain.as_text(), sets)
            == [True, True, False, True, False])


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _program(hlo: str) -> dict:
    """A compiled program's computations without metadata: what runs, not
    where in the source it came from."""
    comps, _ = _computations(hlo)
    return {c: [re.sub(r", metadata=\{[^}]*\}", "", ln) for ln in lines]
            for c, lines in comps.items()}


@pytest.mark.parametrize("sets,steps", [(8192, 512), (1 << 17, 64), (1 << 20, 64)])
def test_jnp_replay_without_admission_is_the_access_scan(sds, sets, steps):
    """``CacheBackend.replay`` without TinyLFU compiles, for a v5e, to the
    program of a plain scan of ``access`` over the chunks (the form it had
    before the admitted step was made public): at the geometries of
    ``getput``, ``ycsbc_tinylfu`` and ``evict``."""
    be = make_backend("jnp", KWayConfig(num_sets=sets, ways=8))

    def fn(state, chunks, enabled, sketch, ttls):
        def step(carry, xs):
            cache, sk = carry
            keys, en = xs
            cache, hit, _, _, ev = be.access(cache, keys, keys.astype(jnp.int32),
                                             None, en)
            return (cache, sk), (jnp.sum(hit.astype(jnp.int32)),
                                 jnp.sum(ev.astype(jnp.int32)))
        (state, sk), (hits, evs) = jax.lax.scan(step, (state, sketch),
                                                (chunks, enabled))
        return hits, evs, state, sk

    state = KWayState(*_lanes(sds, sets, 8), clock=sds(()))
    args = (state, sds((steps, 4096), jnp.uint32), sds((steps, 4096), jnp.bool_),
            sds(()), None)
    want = jax.jit(fn).lower(*args).compile().as_text()
    _, got = _replay_program(sds, sets, None, steps=steps)
    assert _program(got.as_text()) == _program(want)
