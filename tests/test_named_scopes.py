"""The names the profiler trace carries for the program: the ``kway.*``
phase scopes of the cache ops, the router and shard scopes, the kernels'
``pallas_call`` names and the ``cache.access`` host span."""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import backend as backend_mod
from repro.core import kway
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig
from repro.core.policies import Policy

PHASES = {"kway.probe", "kway.hit", "kway.victims", "kway.resolve", "kway.insert"}
CFG = KWayConfig(num_sets=64, ways=8, policy=Policy.LRU)
B = 128
SRC = Path(__file__).resolve().parents[1] / "src"


def _scopes(lowered) -> set:
    return set(re.findall(
        r"kway\.(?:scrub|probe|hit|victims|resolve|insert)(?![a-z])",
        lowered.as_text(debug_info=True)))


def _keys():
    k = jnp.arange(B, dtype=jnp.uint32) * jnp.uint32(7919)
    return k, k.astype(jnp.int32)


def test_access_carries_every_phase():
    k, v = _keys()
    lo = jax.jit(kway.access, static_argnums=0).lower(
        CFG, kway.make_cache(CFG), k, v)
    assert _scopes(lo) == PHASES


def test_access_with_expiry_lane_adds_the_scrub():
    k, v = _keys()
    lo = jax.jit(kway.access, static_argnums=0).lower(
        CFG, kway.make_cache(CFG, ttl=True), k, v, ttls=jnp.full((B,), 5))
    assert _scopes(lo) == PHASES | {"kway.scrub"}


def test_two_phase_oracle_carries_every_phase():
    k, v = _keys()
    lo = kway.access_two_phase.lower(CFG, kway.make_cache(CFG), k, v)
    assert _scopes(lo) == PHASES


def test_jnp_replay_program_carries_every_phase():
    be = make_backend("jnp", CFG)
    chunks = jnp.arange(2 * B, dtype=jnp.uint32).reshape(2, B)
    en = jnp.ones((2, B), jnp.bool_)
    be.replay(be.init(), chunks, en)
    lo = be._replay_fns[(None, False, False)].lower(
        be.init(), chunks, en, jnp.zeros((), jnp.int32), None)
    assert _scopes(lo) == PHASES


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------

def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                out += _pallas_names(sub)
    return out


def _kernel_cases():
    from repro.core import hierarchy as H
    from repro.kernels import kway_probe as kp
    from repro.kernels import replay as rp
    from repro.kernels.paged_attention import paged_attention

    i32, s, b, t = jnp.int32, 64, 16, 2
    lanes = [jax.ShapeDtypeStruct((s, kp.LANES), i32)] * 4
    q = [jax.ShapeDtypeStruct((b,), i32)]
    state = [jax.ShapeDtypeStruct((s, 8), jnp.uint32)] * 2 \
        + [jax.ShapeDtypeStruct((s, 8), i32)] * 3
    trace = [jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((t, b), jnp.uint32),
             jax.ShapeDtypeStruct((t, b), jnp.bool_)]
    hier = H.HierarchyConfig(l1_sets=8, l1_ways=16)
    l1 = [jax.ShapeDtypeStruct((8, 16), jnp.uint32)] * 2 \
        + [jax.ShapeDtypeStruct((8, 16), i32)] * 3
    bf = jnp.bfloat16
    return {
        "kway_probe": (lambda *a: kp.kway_probe(*a, policy=0, ways=8),
                       lanes + q * 3),
        "kway_fused_probe": (lambda *a: kp.kway_fused_probe(*a, policy=0, ways=8),
                             lanes + q * 5),
        "kway_replay_resident": (
            lambda *a: rp.replay_resident(*a, policy=0, ways=8, num_sets=s,
                                          seed=0)[:2], state + trace),
        "kway_replay_hier": (
            lambda *a: rp.replay_hierarchical(
                *a, policy=0, l1_ways=16, l2_ways=8, l1_sets=hier.l1_sets,
                l2_sets=s, seed=0)[:2], l1 + state + trace),
        "paged_attention": (
            paged_attention,
            [jax.ShapeDtypeStruct((2, 4, 64), bf),
             jax.ShapeDtypeStruct((4, 8, 16, 64), bf),
             jax.ShapeDtypeStruct((4, 8, 16, 64), bf),
             jax.ShapeDtypeStruct((2, 4), i32), jax.ShapeDtypeStruct((2,), i32)]),
    }


@pytest.mark.parametrize("name", ["kway_probe", "kway_fused_probe",
                                  "kway_replay_resident", "kway_replay_hier",
                                  "paged_attention"])
def test_each_kernel_has_its_stable_name(name):
    fn, args = _kernel_cases()[name]
    assert _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr) == [name]


# ---------------------------------------------------------------------------
# the host span
# ---------------------------------------------------------------------------

class _Spans:
    def __init__(self):
        self.names = []

    def __call__(self, name):
        spans = self

        class _Span:
            def __enter__(self):
                spans.names.append(name)

            def __exit__(self, *exc):
                return False
        return _Span()


@pytest.mark.parametrize("name", ["jnp", "pallas", "ref"])
def test_access_span_opens_on_host_calls_only(monkeypatch, name):
    spans = _Spans()
    monkeypatch.setattr(backend_mod.jax.profiler, "TraceAnnotation", spans)
    be = make_backend(name, KWayConfig(num_sets=16, ways=4))
    k = jnp.arange(8, dtype=jnp.uint32)
    st, *_ = be.access(be.init(), k, k.astype(jnp.int32))
    assert spans.names == [backend_mod.ACCESS_SPAN]
    if be.traceable:
        jax.jit(lambda s, k: be.access(s, k, k.astype(jnp.int32)))(st, k)
        assert spans.names == [backend_mod.ACCESS_SPAN]


# ---------------------------------------------------------------------------
# router and shard scopes, on four virtual devices
# ---------------------------------------------------------------------------

_SHARDED = textwrap.dedent("""
    import json, re
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.core.kway import KWayConfig
    from repro.core.sharded import ShardedCache, ShardedConfig

    mesh = jax.make_mesh((4,), ("sets",), axis_types=(AxisType.Auto,))
    sc = ShardedCache(ShardedConfig(cache=KWayConfig(num_sets=64, ways=4),
                                    num_shards=4), mesh=mesh)
    trace = np.arange(64, dtype=np.uint32) * 7919
    sc.replay(trace, 32)
    fn = next(f for k, f in sc._fns.items() if k[0] == "replay")
    chunks = jax.ShapeDtypeStruct((2, 32), jnp.uint32)
    en = jax.ShapeDtypeStruct((2, 32), jnp.bool_)
    state = jax.eval_shape(sc.init)
    sketch = jax.ShapeDtypeStruct((4,), jnp.int32)
    replay = fn.lower(chunks, en, None, state, sketch).as_text(debug_info=True)
    k = jnp.asarray(trace[:32])
    sc.access(sc.init(), k, k.astype(jnp.int32))
    fn = next(f for k, f in sc._fns.items() if k[0] == "step")
    access = fn.lower(jax.ShapeDtypeStruct((32,), jnp.uint32),
                      jax.ShapeDtypeStruct((32,), jnp.int32), state,
                      sketch).as_text(debug_info=True)
    scopes = lambda t: sorted(set(re.findall(
        r"(?:router\\.(?:route|unscatter)|shard\\.access)(?![a-z])", t)))
    print(json.dumps({"replay": scopes(replay), "access": scopes(access)}))
""")


def test_sharded_programs_carry_router_and_shard_scopes():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    got = json.loads(out.stdout.strip().splitlines()[-1])
    # the scanned replay routes and buckets every chunk and counts hits
    # before any unscatter; the access step brings results back in order
    assert got["replay"] == ["router.route", "shard.access"]
    assert got["access"] == ["router.route", "router.unscatter", "shard.access"]


def test_compile_cache_is_keyed_on_op_metadata(monkeypatch, tmp_path):
    """A program read back from the persistent cache keeps the op names of
    the source that asked for it: the key includes the metadata."""
    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    try:
        jax.config.update(flag, False)
        assert compile_cache.enable() == str(tmp_path)
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)
