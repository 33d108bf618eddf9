"""Multi-device behaviour in one subprocess with 8 host devices (conftest
`mesh_check`): shard_map query execution == single-device reference;
compressed psum; elastic mesh degradation."""
import pytest

from conftest import MeshCheck

_QUERY_STEP = """
import numpy as np, jax, jax.numpy as jnp
from repro.db import distributed as dist
from repro.core import poisson_binomial as pb
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
n, G, F = 4096, 64, 512
rng = np.random.default_rng(0)
p = rng.uniform(0.01, 0.99, n).astype(np.float32)
v = rng.integers(0, 4, n).astype(np.float32)
g = rng.integers(0, G, n).astype(np.int32)
step = dist.make_query_step(mesh, max_groups=G, num_freq=F)
pd, vd, gd = dist.shard_columns(mesh, (jnp.asarray(p), jnp.asarray(v), jnp.asarray(g)))
conf, normal, cum, coeffs = jax.block_until_ready(step(pd, vd, gd))
la, an = pb.logcf_terms(jnp.asarray(p), jnp.asarray(v), F)
ref = pb.logcf_finalize(la, an)
assert float(jnp.max(jnp.abs(coeffs - ref))) < 1e-5
ref_conf = 1 - np.exp(np.bincount(g, np.log1p(-p), G))
assert float(jnp.max(jnp.abs(conf - ref_conf))) < 1e-5
mu_ref = np.bincount(g, v * p, G)
assert float(jnp.max(jnp.abs(normal[:, 0] - mu_ref))) < 1e-3
print("OK")
"""

_COMPRESSED_PSUM = """
import numpy as np, jax, jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.train.optimizer import compressed_psum
mesh = jax.make_mesh((8,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
g = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 256)), jnp.float32)
err = jnp.zeros_like(g)
def f(gs, es):
    avg, new_err = compressed_psum(gs[0], es[0], "pod")
    return avg[None], new_err[None]
fn = shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
               out_specs=(P("pod"), P("pod")), check_vma=False)
avg, new_err = fn(g, err)
true_sum = g.mean(0)
# every shard's decompressed average approximates the true mean
rel = float(jnp.abs(avg[0] - true_sum).max() / (jnp.abs(true_sum).max()))
assert rel < 0.05, rel
print("OK")
"""

_ELASTIC_DEGRADE = """
import jax
from repro.train.elastic import degrade_mesh, scale_batch
# full fleet: 8 devices -> (2, 4) mesh? model capped at 4
m = degrade_mesh(jax.devices(), prefer_model=4)
assert m.shape["model"] == 4 and m.shape["data"] == 2, dict(m.shape)
# lose 3 devices -> 5 usable -> (1, 4) with 1 dropped
m2 = degrade_mesh(jax.devices()[:5], prefer_model=4)
assert m2.shape["model"] == 4 and m2.shape["data"] == 1, dict(m2.shape)
assert scale_batch(64, m) == 32
print("OK")
"""

_SHARDED_TRAIN_STEP = """
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_reduced
from repro.models import api
from repro.sharding import Rules
from repro.train.optimizer import AdamW
from repro.train.trainer import make_train_step
cfg = get_reduced("yi_6b")
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = Rules(mesh, fsdp=True)
opt = AdamW(lr=1e-2, warmup=1)
params = api.init_params(cfg, jax.random.PRNGKey(0))
state = opt.init(params)
key = jax.random.PRNGKey(1)
batch = dict(tokens=jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
             labels=jax.random.randint(key, (8, 16), 0, cfg.vocab_size))
raw = make_train_step(cfg, opt, accum=1, donate=False, jit=False)
def fn(p, s, b):
    with rules.activate():
        return raw(p, s, b)
shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
psh = rules.params_tree(shapes)
params_sharded = jax.tree.map(jax.device_put, params, psh)
with mesh:
    p2, s2, m2 = jax.jit(fn)(params_sharded, state, batch)
p1, s1, m1 = jax.jit(fn)(params, state, batch)
d = max(float(jnp.abs(jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)).max())
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
assert d < 2e-5, d
print("OK", float(m1["loss"]))
"""

MESH_CHECKS = {
    "test_distributed_query_step_matches_reference": MeshCheck(
        8, _QUERY_STEP, seconds=5),
    "test_compressed_psum_under_shard_map": MeshCheck(
        8, _COMPRESSED_PSUM, seconds=5),
    "test_elastic_degrade_mesh": MeshCheck(8, _ELASTIC_DEGRADE, seconds=5),
    "test_sharded_train_step_runs_and_matches_single_device": MeshCheck(
        8, _SHARDED_TRAIN_STEP, seconds=15),
}


@pytest.mark.multidevice
def test_distributed_query_step_matches_reference(mesh_check):
    assert "OK" in mesh_check()


@pytest.mark.multidevice
def test_compressed_psum_under_shard_map(mesh_check):
    assert "OK" in mesh_check()


@pytest.mark.multidevice
def test_elastic_degrade_mesh(mesh_check):
    assert "OK" in mesh_check()


@pytest.mark.multidevice
def test_sharded_train_step_runs_and_matches_single_device(mesh_check):
    """A reduced-arch train step under a 4x2 mesh with the production
    sharding rules == the same step on one device."""
    assert "OK" in mesh_check()
