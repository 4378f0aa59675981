"""Multi-device correctness via subprocess (forces 8 host devices —
cannot run in-process because smoke tests must see 1 device)."""
import os
import subprocess
import sys


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_config
from repro.models.transformer import init_params
from repro.models.sharding import mesh_context
from repro.training.train_step import next_token_loss

cfg = get_config('tinyllama-1.1b').reduced()
params = init_params(cfg, jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (4, 33), 0, cfg.vocab_size)

l_single, _ = jax.jit(lambda p, t: next_token_loss(
    cfg, p, t, compute_dtype=jnp.float32, q_block=64))(params, toks)

from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(data=4, model=2)
from repro.launch.shard_rules import tree_shardings
params_sh = jax.device_put(params, tree_shardings(params, mesh))
toks_sh = jax.device_put(toks, NamedSharding(mesh, P('data', None)))
with mesh_context(mesh):
    fn = jax.jit(lambda p, t: next_token_loss(
        cfg, p, t, compute_dtype=jnp.float32, q_block=64))
    l_shard, _ = fn(params_sh, toks_sh)
np.testing.assert_allclose(float(l_single), float(l_shard), rtol=2e-4)
print('OK', float(l_single), float(l_shard))
""")
    assert "OK" in out


def test_moe_ep_multi_device_matches_dense():
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.models import moe as moe_mod

base = get_config('qwen2-moe-a2.7b').reduced()
cfg = dataclasses.replace(base, moe=dataclasses.replace(
    base.moe, num_experts=4, top_k=2, capacity_factor=16.0))
p = moe_mod.init_moe_params(jax.random.key(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.key(1), (32, cfg.d_model))
dense, _ = moe_mod.routed_dense(cfg, p, x)
mesh = jax.make_mesh((4, 2), ('data', 'model'))   # EP over model=2
ep, _ = jax.jit(lambda xx: moe_mod.routed_ep(cfg, p, xx, mesh))(x)
np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                           rtol=3e-4, atol=3e-4)
print('OK')
""")
    assert "OK" in out


def test_moe_ep_uneven_experts_multi_device():
    """60-expert Qwen config over model=8: expert padding path."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.models import moe as moe_mod

base = get_config('qwen2-moe-a2.7b').reduced()
cfg = dataclasses.replace(base, moe=dataclasses.replace(
    base.moe, num_experts=6, top_k=2, capacity_factor=16.0))
p = moe_mod.init_moe_params(jax.random.key(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.key(1), (32, cfg.d_model))
dense, _ = moe_mod.routed_dense(cfg, p, x)
mesh = jax.make_mesh((2, 4), ('data', 'model'))   # 6 experts over tp=4
ep, _ = jax.jit(lambda xx: moe_mod.routed_ep(cfg, p, xx, mesh))(x)
np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                           rtol=3e-4, atol=3e-4)
print('OK')
""")
    assert "OK" in out


def test_moe_ep_all_axes_matches_dense():
    """Wide EP (experts over model AND data, resident weights) — the
    ep_all_axes beyond-paper optimization must stay numerically exact."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.launch import optflags
from repro.models import moe as moe_mod

optflags.set_flags(['ep_all_axes', 'resident_weights'])
base = get_config('qwen2-moe-a2.7b').reduced()
cfg = dataclasses.replace(base, moe=dataclasses.replace(
    base.moe, num_experts=8, top_k=2, capacity_factor=16.0))
p = moe_mod.init_moe_params(jax.random.key(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.key(1), (32, cfg.d_model))
dense, _ = moe_mod.routed_dense(cfg, p, x)
mesh = jax.make_mesh((2, 4), ('data', 'model'))   # EP over 8 devices
ep, _ = jax.jit(lambda xx: moe_mod.routed_ep(cfg, p, xx, mesh))(x)
optflags.set_flags([])
np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                           rtol=3e-4, atol=3e-4)
print('OK')
""")
    assert "OK" in out


def test_sharded_decode_matches_single_device():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_config, INPUT_SHAPES
from repro.models.transformer import init_params, apply_model
from repro.models.sharding import mesh_context
from repro.serving.kv_cache import init_cache
from repro.launch.shard_rules import tree_shardings, cache_spec

cfg = get_config('gemma2-9b').reduced()
params = init_params(cfg, jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
cache = init_cache(cfg, 8, 32)
l1, cache1, _ = apply_model(cfg, params, toks[:, :15], cache, 0)
l1d, _, _ = apply_model(cfg, params, toks[:, 15:16], cache1, 15)

from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(data=4, model=2)
params_sh = jax.device_put(params, tree_shardings(params, mesh))
def csh(path, leaf):
    import jax.tree_util as jtu
    name = None
    for k in reversed(path):
        if isinstance(getattr(k, 'key', None), str):
            name = k.key; break
    return jax.device_put(leaf, NamedSharding(
        mesh, cache_spec(name, leaf.shape, mesh, batch=8)))
import jax.tree_util as jtu
cache_sh = jtu.tree_map_with_path(csh, init_cache(cfg, 8, 32))
with mesh_context(mesh):
    fn = jax.jit(lambda p, t, c, pos: apply_model(cfg, p, t, c, pos))
    _, cache_sh, _ = fn(params_sh, toks[:, :15], cache_sh, 0)
    l2d, _, _ = fn(params_sh, toks[:, 15:16], cache_sh, 15)
np.testing.assert_allclose(np.asarray(l1d), np.asarray(l2d),
                           rtol=3e-3, atol=3e-3)
print('OK')
""")
    assert "OK" in out
