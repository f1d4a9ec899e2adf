"""The paper's Figure-1 claim, verified on compiled HLO: with requests
routed to the device owning their sets (the paper's hash routing), K-way
cache operations across 8 devices compile to ZERO collectives.

Each device owns an independent sub-cache (sets are independent — §1); the
global cache is their disjoint union, and ``shard_map`` expresses exactly
the "Alice and Bob never synchronize" execution.  Runs in a subprocess
(device count must be fixed before jax initializes).
"""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import re
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import kway
from repro.core.kway import KWayConfig
from repro.core.policies import Policy

NDEV = 8
mesh = jax.make_mesh((NDEV,), ("sets",))
cfg = KWayConfig(num_sets=16, ways=8, policy=Policy.LRU)  # per-device cache

def local_access(keys, vals, *leaves):
    st = kway.KWayState(*[l[0] for l in leaves[:-1]], clock=leaves[-1][0])
    st, hit, out, _, _ = kway.access(cfg, st, keys[0], vals[0])
    new_leaves = (st.keys, st.fprint, st.vals, st.meta_a, st.meta_b)
    return (hit[None], out[None]) + tuple(l[None] for l in new_leaves) + (
        st.clock[None],)

st0 = kway.make_cache(cfg)
leaves = [jnp.broadcast_to(l, (NDEV,) + l.shape) for l in
          (st0.keys, st0.fprint, st0.vals, st0.meta_a, st0.meta_b)]
clock = jnp.zeros((NDEV,), jnp.int32)
keys = jnp.ones((NDEV, 32), jnp.uint32)   # pre-routed per device
vals = jnp.ones((NDEV, 32), jnp.int32)

fn = shard_map(
    local_access, mesh=mesh,
    in_specs=(P("sets"), P("sets")) + (P("sets"),) * 6,
    out_specs=(P("sets"),) * 8,
)
compiled = jax.jit(fn).lower(keys, vals, *leaves, clock).compile()
txt = compiled.as_text()
colls = re.findall(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)",
    txt,
)
assert not colls, f"collectives found: {colls}"
print("ZERO collectives across", NDEV, "devices: OK")
"""


def test_kway_set_axis_zero_collectives():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=600, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "HOME": "/root"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ZERO collectives across 8 devices: OK" in r.stdout
