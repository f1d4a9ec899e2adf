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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hierarchy as H
from repro.core.admission import for_capacity
from repro.core.backend import make_backend
from repro.core.kway import KWayConfig
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
