"""Sharding-rule unit tests (AbstractMesh — no devices needed) + a mini
multi-device dry-run integration test (subprocess, 8 fake devices)."""
import subprocess
import sys

from conftest import subproc_env

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.sharding import AbstractMesh
from repro.configs import ARCHS, get_arch
from repro.launch.shardings import (batch_specs, cache_specs, param_specs,
                                    spec_for_param, state_specs, zero_spec)
from repro.models import model as Mdl



MESH = AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_specs_divisible_everywhere(arch):
    """Every sharded dim must divide by its mesh axis; big matrices must
    actually BE sharded (vocab/ff/heads/experts over model)."""
    cfg = get_arch(arch)
    shapes = jax.eval_shape(
        lambda: Mdl.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    specs = param_specs(shapes, MESH)
    sl, _ = jax.tree_util.tree_flatten_with_path(specs)
    hl, _ = jax.tree_util.tree_flatten_with_path(shapes)
    n_big_unsharded = 0
    for (path, spec), (_, leaf) in zip(sl, hl):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is not None:
                size = MESH.shape[ax] if isinstance(ax, str) else \
                    int(np.prod([MESH.shape[a] for a in ax]))
                assert dim % size == 0, (arch, jax.tree_util.keystr(path))
        name = jax.tree_util.keystr(path)
        if (leaf.size > 4e6 and all(a is None for a in tuple(spec))
                and "router" not in name):   # router is replicated by design
            n_big_unsharded += 1
    assert n_big_unsharded == 0, f"{arch}: {n_big_unsharded} big leaves unsharded"


def test_zero_spec_adds_data_axis():
    spec = zero_spec(P("model", None), (262144, 1152), MESH, ("data",))
    assert tuple(spec) in (("model", "data"), ("model", ("data",)))
    # non-divisible dim stays replicated
    spec = zero_spec(P("model", None), (262144, 7), MESH, ("data",))
    assert tuple(spec) == ("model", None)


def test_cache_and_batch_specs():
    cfg = get_arch("gemma3-1b")
    caches = jax.eval_shape(lambda: Mdl.init_caches(cfg, 128, 1024, jnp.bfloat16))
    specs = cache_specs(caches, MESH, 128, ("data",))
    kspec = specs["blocks"]["pos5"]["k"]
    assert tuple(kspec)[1] in ("data", ("data",))  # batch dim (after stack)
    # gemma3-1b has kv=1 head (not divisible by 16) -> falls back to
    # sequence-dim sharding of the cache
    assert tuple(kspec)[2] is None and tuple(kspec)[3] == "model"
    b = batch_specs({"tokens": jax.ShapeDtypeStruct((128, 64), jnp.int32)},
                    MESH, ("data",))
    assert tuple(b["tokens"]) in ((("data",), None), ("data", None))
    # batch=1 (long_500k): replicated
    b1 = batch_specs({"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)},
                     MESH, ("data",))
    assert tuple(b1["tokens"]) == (None, None)


def test_mini_dryrun_8dev():
    """Smoke config lower+compile on a (2, 4) mesh with collectives."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.models import model as Mdl
from repro.models.sharding import default_rules, use_rules
from repro.launch.shardings import batch_specs, state_specs, to_shardings
from repro.roofline.analysis import parse_collectives, roofline_from
from repro.train.train_step import TrainConfig, TrainState, train_step
from repro.train.optimizer import adamw_init

from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_arch("moonshot-v1-16b-a3b").smoke()
tc = TrainConfig(remat=True, microbatches=1)
rules = default_rules(data_axes=("data",), mesh=mesh)

def step(state, batch):
    with use_rules(rules):
        return train_step(cfg, tc, state, batch, mesh=mesh,
                          data_axes=("data",))

st = jax.eval_shape(lambda: TrainState(
    params=Mdl.init_params(cfg, jax.random.PRNGKey(0), jnp.float32),
    opt=adamw_init(Mdl.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))))
st_sh = to_shardings(state_specs(st, mesh, ("data",)), mesh)
batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
b_sh = to_shardings(batch_specs(batch, mesh, ("data",)), mesh)
lowered = jax.jit(step, in_shardings=(st_sh, b_sh),
                  donate_argnums=(0,)).lower(st, batch)
compiled = lowered.compile()
cost = compiled.cost_analysis()
roof = roofline_from(cost, compiled.as_text())
assert roof.flops > 0
assert roof.n_collectives > 0, "SPMD must emit collectives"
print("OK", int(roof.flops), roof.n_collectives)
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=subproc_env(),
                         cwd=".", timeout=600)
    assert "OK" in out.stdout, out.stderr[-3000:]
