"""Multi-device tests (sharded == unsharded equivalence, elastic rescale,
pipeline parallelism, compressed all-reduce).

These REQUIRE virtual devices, and the device count must be set before jax
initializes — so each test runs a small script in a subprocess with
--xla_force_host_platform_device_count (the main pytest process keeps the
real single CPU device, per the project rules).
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

# every test here spawns a fresh multi-device jax subprocess
pytestmark = pytest.mark.slow


def run_script(body: str, devices: int = 8, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", body], env=env, timeout=timeout,
                       capture_output=True, text=True)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.configs.base import SHAPES
from repro.models import get_model_def
from repro.models.module import init_params
from repro.launch.mesh import make_mesh_for
from repro.launch.steps import make_train_step, state_specs
from repro.train.data import SyntheticLMData

SHAPES["tiny"] = dict(seq_len=64, global_batch=8, kind="train")
cfg = smoke_config("granite-moe-3b-a800m").replace(n_experts_padded=8)
md = get_model_def(cfg)

def run(mesh):
    jax.set_mesh(mesh)
    step, opt = make_train_step(md, cfg, warmup=1)
    sds, shard = state_specs(md, cfg, mesh)
    params = jax.jit(lambda k: init_params(md.specs(cfg), k),
                     out_shardings=shard["params"])(jax.random.PRNGKey(0))
    state = {"params": params, "opt": jax.jit(opt.init, out_shardings=shard["opt"])(params)}
    data = SyntheticLMData(cfg, "tiny", mesh, seed=1)
    with mesh:
        jstep = jax.jit(step, in_shardings=(shard, None))
        losses = []
        for i in range(3):
            state, m = jstep(state, data.batch(i))
            losses.append(float(m["loss"]))
    return losses

l1 = run(make_mesh_for(1, 1))
l8 = run(make_mesh_for(8, 2))
print("single:", l1)
print("sharded:", l8)
assert all(abs(a - b) / abs(a) < 5e-3 for a, b in zip(l1, l8)), (l1, l8)
print("OK")
""")


def test_elastic_rescale_bit_identical():
    run_script("""
import jax, jax.numpy as jnp, tempfile
from repro.configs import smoke_config
from repro.models import get_model_def
from repro.models.module import init_params
from repro.launch.mesh import make_mesh_for
from repro.launch.steps import state_specs
from repro.launch.elastic import rescale_state, verify_rescale
from repro.train.checkpoint import save_checkpoint

cfg = smoke_config("codeqwen1.5-7b")
md = get_model_def(cfg)
mesh_a = make_mesh_for(8, 4)
sds, shard = state_specs(md, cfg, mesh_a)
params = jax.jit(lambda k: init_params(md.specs(cfg), k),
                 out_shardings=shard["params"])(jax.random.PRNGKey(0))
state = {"params": params, "opt": {"m": params, "v": params,
                                   "step": jnp.zeros((), jnp.int32)}}
d = tempfile.mkdtemp()
save_checkpoint(d, state, 7)
# restore onto a DIFFERENT mesh shape (2-way model instead of 4-way)
mesh_b = make_mesh_for(8, 2)
state_b, step = rescale_state(d, md, cfg, mesh_b)
assert step == 7
assert verify_rescale(state, state_b)
print("OK")
""")


def test_pipeline_parallelism_matches_sequential():
    run_script("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh_for
from repro.sharding.pipeline import pipeline_forward

mesh = jax.make_mesh((4,), ("pipe",),
                     axis_types=(jax.sharding.AxisType.Auto,))
S, MB, D = 4, 3, 16
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (S, D, D)) / D**0.5

def stage(w, h):
    return jnp.tanh(h @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (5, MB, D))  # 5 microbatches
out = pipeline_forward(stage, ws, x, mesh, axis="pipe")

# sequential oracle
ref = x
for s in range(S):
    ref = jnp.tanh(ref @ ws[s])
err = float(jnp.abs(out - ref).max())
assert err < 1e-5, err
print("OK")
""", devices=4)


def test_compressed_allreduce_shard_map():
    run_script("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.sharding.compression import compressed_psum_leaf, compressed_mean_ref

mesh = jax.make_mesh((4,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))  # per-pod grads
errs = jnp.zeros_like(g)

def f(g_local, e_local):
    m, ne = compressed_psum_leaf(g_local[0], e_local[0], "pod")
    return m[None], ne[None]

fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                           out_specs=(P("pod"), P("pod"))))
mean_est, new_err = fn(g, errs)
ref_mean, ref_err = compressed_mean_ref(g, errs)
# every pod computed the same mean estimate; matches the reference exactly
est0 = np.asarray(mean_est)[0]
assert np.allclose(np.asarray(mean_est), est0[None], atol=1e-5)
assert np.allclose(est0, np.asarray(ref_mean), atol=1e-4)
# error feedback: the TIME-AVERAGED estimate converges to the true mean
true = np.asarray(g).mean(0)
acc = np.zeros(64)
errs_t = errs
steps = 60
for _ in range(steps):
    est, errs_t = fn(g, errs_t)
    acc += np.asarray(est)[0]
drift = np.abs(acc / steps - true).max()
assert drift < 0.05, drift
print("OK")
""", devices=4)


def test_production_mesh_shapes():
    run_script("""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert dict(m1.shape) == {"data": 16, "model": 16}
m2 = make_production_mesh(multi_pod=True)
assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
assert m2.size == 512
print("OK")
""", devices=512)


def test_distributed_camformer_matches_local():
    """H3 (EXPERIMENTS §Perf): shard_map CAM search == single-device path."""
    run_script("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import smoke_config
from repro.core.backend import get_backend
from repro.core import bacam, sign_pm1
from repro.launch.mesh import make_mesh_for

mesh = make_mesh_for(4, 2)  # data=2, model=2
jax.set_mesh(mesh)
cfg = smoke_config("codeqwen1.5-7b", head_dim=128, n_heads=4,
                   n_kv_heads=2).replace(attn_backend="camformer", k_top=8,
                                         group_size=4, stage1_k=2)
bk = get_backend(cfg.backend)
B, HKV, H, S, D = 1, 2, 4, 64, 128
k_raw = jax.random.normal(jax.random.PRNGKey(3), (B, HKV, S, D))
cache = {
    "k_packed": bacam.pack_bits(sign_pm1(k_raw)),
    "v": jax.random.normal(jax.random.PRNGKey(1), (B, HKV, S, D)),
    "k_scale": jnp.mean(jnp.abs(k_raw), axis=(2, 3)),
}
q = jax.random.normal(jax.random.PRNGKey(2), (B, H, 1, D))
pos = jnp.full((B, 1), 40, jnp.int32)
kvl = jnp.full((B,), 41, jnp.int32)
with mesh:
    local = jax.jit(lambda q, c: bk._cache_attend(
        q, c, kvl, pos, cfg))(q, cache)
    sh = NamedSharding(mesh, P(None, None, ("data", "model"), None))
    cache_sh = dict(cache)
    cache_sh["k_packed"] = jax.device_put(cache["k_packed"], sh)
    cache_sh["v"] = jax.device_put(cache["v"], sh)
    dist = jax.jit(lambda q, c: bk._distributed_attend(
        q, c, kvl, pos, cfg))(q, cache_sh)
err = float(jnp.abs(local - dist).max())
assert err < 1e-4, err
print("OK")
""", devices=4)
