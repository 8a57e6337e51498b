"""Multi-device collective tests (pipelined ring/PBT broadcast, resharding).

These need >1 device, so they run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (built by the
``multidevice_env`` conftest fixture, which skips when the forced device
count can't be satisfied) — the main test process keeps seeing 1 CPU
device, per the dry-run isolation rule.
"""

import subprocess
import sys

import pytest

_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from functools import partial
from repro.core.replication import ring_broadcast, pbt_broadcast, replicate
from repro.core.packets import ReplStrategy

mesh = jax.make_mesh((8,), ("r",))
rng = np.random.default_rng(0)
data = rng.standard_normal((8, 4, 32)).astype(np.float32)
x = jax.device_put(jnp.asarray(data), NamedSharding(mesh, P("r")))

for fn in (ring_broadcast, pbt_broadcast):
    for nc in (1, 4, 16):
        body = partial(fn, axis_name="r", num_chunks=nc, axis_size=8)
        out = np.asarray(jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("r"), out_specs=P("r")))(x))
        for i in range(8):
            assert np.array_equal(out[i], data[0]), (fn.__name__, nc, i)

out = np.asarray(replicate(x, mesh, "r", ReplStrategy.PBT, num_chunks=4))
assert all(np.array_equal(out[i], data[0]) for i in range(8))

# elastic reshard: move a sharded tree onto a smaller mesh
from repro.runtime.elastic import build_mesh, reshard_state
tree = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                            NamedSharding(mesh, P("r", None)))}
small = build_mesh(list(jax.devices())[:4], model_parallel=2)
assert dict(small.shape) == {"data": 2, "model": 2}
tree2 = reshard_state(tree, small)
assert np.array_equal(np.asarray(tree2["w"]), np.arange(64.0).reshape(8, 8))

# data pipeline with sharded device_put
from repro.data.pipeline import DataPipeline, PipelineConfig, SyntheticSource
sh = {"tokens": NamedSharding(mesh, P("r", None)),
      "labels": NamedSharding(mesh, P("r", None))}
pipe = DataPipeline(SyntheticSource(100, seed=3),
                    PipelineConfig(batch=8, seq=16), shardings=sh)
b = next(iter(pipe))
assert b["tokens"].sharding.spec == P("r", None)
pipe.close()
print("MULTIDEVICE_OK")
"""


@pytest.mark.slow
def test_multidevice_collectives(multidevice_env):
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=multidevice_env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MULTIDEVICE_OK" in proc.stdout


_MOE_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.models import moe as moe_mod

mesh = jax.make_mesh((2, 4), ("data", "model"))
E, K, d, ff, B, S = 8, 2, 32, 64, 4, 16
p = moe_mod.moe_init(jax.random.PRNGKey(0), d, ff, E)
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.float32)
want = moe_mod.moe_apply(p, x, E, K, dense_fallback=True)

xs = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
ps = dict(p)
ps["w_gate"] = jax.device_put(p["w_gate"], NamedSharding(mesh, P("model", "data", None)))
ps["w_up"] = jax.device_put(p["w_up"], NamedSharding(mesh, P("model", "data", None)))
ps["w_down"] = jax.device_put(p["w_down"], NamedSharding(mesh, P("model", None, "data")))
ps["router"] = {"w": jax.device_put(p["router"]["w"], NamedSharding(mesh, P("data", None)))}
with mesh:
    got = jax.jit(lambda pp, xx: moe_mod.moe_ep_apply(
        pp, xx, E, K, 8.0, mesh, ("data",), "model"))(ps, xs)
np.testing.assert_allclose(np.asarray(got, np.float32),
                           np.asarray(want, np.float32), rtol=3e-2, atol=3e-2)

def loss(pp):
    return (moe_mod.moe_ep_apply(pp, xs, E, K, 8.0, mesh, ("data",), "model") ** 2).sum()
with mesh:
    g = jax.jit(jax.grad(loss))(ps)
gn = jax.tree.reduce(lambda a, v: a + float(jnp.sum(jnp.abs(v))), g, 0.0)
assert np.isfinite(gn) and gn > 0
print("MOE_EP_OK")
"""


@pytest.mark.slow
def test_moe_ep_shardmap(multidevice_env):
    """Explicit expert-parallel all-to-all dataflow matches the dense
    reference (no-drop capacity) and differentiates, on a 2x4 mesh."""
    proc = subprocess.run(
        [sys.executable, "-c", _MOE_SCRIPT], env=multidevice_env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MOE_EP_OK" in proc.stdout


_RING_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel.collectives import (ring_all_gather, ring_reduce_scatter,
                                        ring_all_reduce, make_ring_collective)
mesh = jax.make_mesh((8,), ("r",))
rng = np.random.default_rng(0)
x = rng.standard_normal((16, 4)).astype(np.float32)
xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("r")))
ag = make_ring_collective(ring_all_gather, mesh, "r")(xs)
assert np.allclose(np.asarray(ag), x)
xr = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))
rs = make_ring_collective(ring_reduce_scatter, mesh, "r")(xr)
assert np.allclose(np.asarray(rs), 8 * x)
ar = make_ring_collective(ring_all_reduce, mesh, "r")(xr)
assert np.allclose(np.asarray(ar), 8 * x)
vs = jax.device_put(jnp.asarray(rng.standard_normal((64, 3)).astype(np.float32)),
                    NamedSharding(mesh, P("r")))
out = jax.jit(jax.shard_map(lambda v: ring_all_reduce(v, "r", 8), mesh=mesh,
                            in_specs=P("r"), out_specs=P("r"),
                            check_vma=False))(vs)
blocks = np.asarray(vs).reshape(8, 8, 3)
want = blocks.sum(axis=0)
got = np.asarray(out).reshape(8, 8, 3)
assert all(np.allclose(got[i], want, atol=1e-5) for i in range(8))
print("RING_OK")
"""


@pytest.mark.slow
def test_ring_collectives(multidevice_env):
    """Paper-style pipelined ring all-gather/reduce-scatter/all-reduce."""
    proc = subprocess.run(
        [sys.executable, "-c", _RING_SCRIPT], env=multidevice_env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "RING_OK" in proc.stdout
