"""Distribution: sharding specs are divisibility-safe; a tiny model jits on
a small multi-device mesh (subprocess, isolated device-count flag)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.distributed import sharding as shd
from repro.launch import specs as S

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    """Just enough of a Mesh for spec construction."""
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_divisible(arch):
    cfg = get_config(arch)
    mesh = FakeMesh((16, 16), ("data", "model"))
    params_sh = S.abstract_params(cfg)
    specs = shd.param_specs(mesh, params_sh)
    flat_p = jax.tree_util.tree_leaves(params_sh)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    sizes = {"data": 16, "model": 16}
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s, strict=True):
        for dim, ax in zip(leaf.shape, tuple(spec), strict=False):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            total = int(np.prod([sizes[a] for a in axes]))
            assert dim % total == 0, (arch, leaf.shape, spec)


def test_cache_specs_divisible_batch1():
    """long_500k: batch=1 must not be sharded; sequence takes the axes."""
    from repro.configs import INPUT_SHAPES
    cfg = S.resolved_config(get_config("gemma2-2b"), INPUT_SHAPES["long_500k"])
    mesh = FakeMesh((16, 16), ("data", "model"))
    caches = jax.eval_shape(
        lambda: __import__("repro.models.model", fromlist=["m"]).init_cache(
            cfg, 1, 524288))
    specs = shd.cache_specs(mesh, caches)
    flat_c = jax.tree_util.tree_leaves(caches)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    sizes = {"data": 16, "model": 16}
    for leaf, spec in zip(flat_c, flat_s, strict=True):
        for dim, ax in zip(leaf.shape, tuple(spec), strict=False):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            total = int(np.prod([sizes[a] for a in axes]))
            assert dim % total == 0, (leaf.shape, spec)


SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import model as M
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models.common import activation_mesh

cfg = get_config("internlm2-1.8b").reduced(d_model=256, num_heads=4)
mesh = make_mesh((2, 4), ("data", "model"))
params = M.init_params(jax.random.PRNGKey(0), cfg)
pspecs = shd.param_specs(mesh, params)
ns = lambda s: NamedSharding(mesh, s)
p_sh = jax.tree.map(ns, pspecs, is_leaf=lambda x: isinstance(x, P))
params = jax.tree.map(lambda a, s: jax.device_put(a, s), params, p_sh)
batch = {"tokens": jax.device_put(
    jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
    ns(P("data", None)))}
with activation_mesh(mesh, shd.activation_rules(mesh)):
    loss, _ = jax.jit(lambda p, b: M.loss_fn(p, cfg, b))(params, batch)
print(json.dumps({"loss": float(loss), "finite": bool(jnp.isfinite(loss))}))
"""


def test_tiny_model_runs_on_8_device_mesh():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SUBPROC_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["finite"]
