"""int8 ring all-reduce + error feedback (subprocess: needs 8 devices)."""
import subprocess
import sys

from conftest import subproc_env

import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.train.collectives import _quantize



@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.floats(0.01, 1000.0))
def test_quantize_error_bound(seed, scale):
    """Property: |x - dequant(quant(x))| <= max|x|/254 elementwise."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray((rng.normal(0, 1, 64) * scale).astype(np.float32))
    q, s = _quantize(x)
    err = np.abs(np.asarray(x) - np.asarray(q, np.float32) * float(s))
    assert err.max() <= float(jnp.max(jnp.abs(x))) / 254.0 + 1e-6


def test_ring_allreduce_8dev():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.compat import make_mesh
from repro.train.collectives import ring_allreduce, compressed_grad_allreduce

mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1003)) * 3.0
fn = shard_map(lambda xl: ring_allreduce(xl[0], "data")[None], mesh=mesh,
               in_specs=P("data", None), out_specs=P("data", None),
               check_vma=False)
got = np.asarray(fn(x))
want = np.asarray(jnp.sum(x, 0))
rel = np.abs(got[0] - want).max() / np.abs(want).max()
assert rel < 0.05, rel
assert np.array_equal(got, np.broadcast_to(got[0], got.shape)), "ranks differ"

# error feedback: mean of (grads + err) over steps converges to true mean
def df(xl):
    g = {"w": xl[0]}
    mean, err = compressed_grad_allreduce(g, "data")
    return mean["w"][None], err["w"][None]
fn2 = shard_map(df, mesh=mesh, in_specs=P("data", None),
                out_specs=(P("data", None), P("data", None)), check_vma=False)
mean, err = fn2(x)
true = np.asarray(jnp.mean(x, 0))
assert np.abs(np.asarray(mean)[0] - true).max() / np.abs(true).max() < 0.05
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=subproc_env(),
                         cwd=".", timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]
