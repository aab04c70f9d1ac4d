"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels.minplus.ops import minplus
from repro.kernels.minplus.ref import minplus_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


# --- minplus ---------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128, 128, 128), (64, 100, 36),
                                   (256, 128, 384), (13, 17, 29), (1, 1, 1)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_minplus_shapes(shape, dtype):
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    a = jnp.asarray(rng.uniform(0, 10, (m, k)).astype(dtype))
    b = jnp.asarray(rng.uniform(0, 10, (k, n)).astype(dtype))
    got = minplus(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(minplus_ref(a.astype(jnp.float32),
                                                      b.astype(jnp.float32))),
                               rtol=1e-6, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 80), st.integers(2, 80), st.integers(0, 10**6))
def test_minplus_property(m, n, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.uniform(0, 100, (m, n)).astype(np.float32))
    b = jnp.asarray(rng.uniform(0, 100, (n, m)).astype(np.float32))
    got = np.asarray(minplus(a, b, interpret=True))
    want = np.asarray(minplus_ref(a, b))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-4)


def test_minplus_batched_kernel_matches_ref():
    """Batched Pallas kernel (grid over batch axis, interpret mode on CPU)
    vs the vmapped jnp oracle."""
    from repro.kernels.minplus.ops import minplus_batched
    from repro.kernels.minplus.ref import minplus_batched_ref
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.uniform(0, 10, (3, 20, 33)).astype(np.float32))
    b = jnp.asarray(rng.uniform(0, 10, (3, 33, 17)).astype(np.float32))
    got = minplus_batched(a, b, block=16, force_kernel=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(minplus_batched_ref(a, b)),
                               rtol=1e-6, atol=1e-5)


def test_batched_apsp_kernel_path_matches_scipy():
    """use_kernel=True routes the batched APSP through kernels.minplus
    (oracle on CPU, Pallas grid-over-batch on TPU)."""
    from repro.core.batcheval import adjacency_batch_from_rings, diameters
    from repro.core.construction import random_ring
    from repro.core.diameter import diameter_scipy
    from repro.core.topology import make_latency
    rng = np.random.default_rng(4)
    w = make_latency("uniform", 24, seed=8)
    genomes = np.stack([[random_ring(rng, 24)] for _ in range(4)])
    batch = adjacency_batch_from_rings(w, genomes)
    got = diameters(batch, use_kernel=True)
    for i in range(4):
        assert float(got[i]) == pytest.approx(diameter_scipy(batch[i]),
                                              rel=1e-5)


def test_minplus_apsp_integration():
    """The kernel plugged into the APSP loop gives scipy's diameter."""
    from repro.core.diameter import apsp, diameter_scipy, adjacency_from_rings
    from repro.core.topology import make_latency
    from repro.core.construction import random_ring
    w = make_latency("uniform", 40, seed=7)
    adj = adjacency_from_rings(w, [random_ring(np.random.default_rng(0), 40)])
    d_kernel = np.asarray(apsp(jnp.asarray(adj), use_kernel=True))
    assert float(d_kernel.max()) == pytest.approx(diameter_scipy(adj), rel=1e-5)


@pytest.mark.parametrize("shape", [(100, 36, 20), (37, 53, 29), (5, 130, 7)])
def test_minplus_adaptive_block_bit_identical(shape):
    """Regression for the pad-to-128 waste: with the default (adaptive)
    block the padded kernel output must be BIT-identical to the jnp oracle
    for non-multiple shapes — min over the INF-padded candidates is exact,
    so any deviation means the padding leaked into the reduction."""
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    a = jnp.asarray(rng.uniform(0, 10, (m, k)).astype(np.float32))
    b = jnp.asarray(rng.uniform(0, 10, (k, n)).astype(np.float32))
    got = np.asarray(minplus(a, b, interpret=True))
    assert np.array_equal(got, np.asarray(minplus_ref(a, b))), shape


def test_minplus_batched_adaptive_block_bit_identical():
    from repro.kernels.minplus.ops import minplus_batched
    from repro.kernels.minplus.ref import minplus_batched_ref
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.uniform(0, 10, (2, 45, 70)).astype(np.float32))
    b = jnp.asarray(rng.uniform(0, 10, (2, 70, 31)).astype(np.float32))
    got = np.asarray(minplus_batched(a, b, force_kernel=True))
    assert np.array_equal(got, np.asarray(minplus_batched_ref(a, b)))


def test_adaptive_block_sizes():
    """The auto block covers small operands without padding to 128, and
    the auto tile is one the chip accepts: a single whole-matrix block at a
    16-multiple up to the cap, else the 128-multiple that pads least."""
    from repro.kernels.minplus.ops import _auto_block, default_tile
    assert _auto_block(20, 33) == 40       # ceil(33 -> /8) is 40, not 128
    assert _auto_block(7, 5) == 8
    assert _auto_block(300, 40) == 128     # large dims still cap at 128
    assert default_tile(256) == 256
    assert default_tile(100) == 112        # one block, bf16 sublane multiple
    assert default_tile(300) == 128        # 3 x 128 pads to 384, 2 x 256 to 512
    assert default_tile(986) == 256        # 1024 either way: fewer blocks
    assert default_tile(1024) == 256
    assert default_tile(4096) == 256


# --- tiled (blocked) Floyd-Warshall APSP ------------------------------------

def _ring_adj(n, seed, k_rings=2):
    from repro.core.construction import random_ring
    from repro.core.diameter import adjacency_from_rings
    from repro.core.topology import make_latency
    rng = np.random.default_rng(seed)
    w = make_latency("uniform", n, seed=seed)
    return adjacency_from_rings(w, [random_ring(rng, n)
                                    for _ in range(k_rings)])


@pytest.mark.parametrize("n,tile", [(24, 8), (37, 16), (64, 16)])
def test_apsp_tiled_kernel_bitwise_matches_ref(n, tile):
    """Pallas blocked FW (interpret on CPU) vs the jnp twin: the two run
    the same blocked schedule over the same candidates, so the float32
    results must be bit-identical — non-multiple N exercises the INF pad."""
    from repro.kernels.minplus.ops import apsp_tiled
    adj = jnp.asarray(_ring_adj(n, seed=n))
    ref = np.asarray(apsp_tiled(adj, tile=tile))
    ker = np.asarray(apsp_tiled(adj, tile=tile, force_kernel=True,
                                interpret=True))
    assert np.array_equal(ref, ker), (n, tile)
    sym = np.asarray(apsp_tiled(adj, tile=tile, symmetric=True))
    assert np.array_equal(ref, sym), (n, tile)


def test_apsp_tiled_matches_scipy():
    from scipy.sparse.csgraph import shortest_path
    from repro.core.diameter import INF, is_edge
    from repro.kernels.minplus.ops import apsp_tiled
    adj = _ring_adj(30, seed=5)
    got = np.asarray(apsp_tiled(jnp.asarray(adj), tile=8))
    graph = np.where(np.asarray(is_edge(adj)), adj, 0.0)
    want = shortest_path(graph, method="D", directed=False)
    np.testing.assert_allclose(np.where(got >= INF / 2, np.inf, got), want,
                               rtol=1e-5)


# --- flash attention --------------------------------------------------------

CASES = [
    dict(b=1, hq=2, hkv=2, tq=128, tk=128, d=128, causal=True, window=None),
    dict(b=2, hq=4, hkv=2, tq=256, tk=256, d=64, causal=True, window=None),
    dict(b=1, hq=4, hkv=1, tq=200, tk=200, d=80, causal=True, window=96),
    dict(b=1, hq=2, hkv=2, tq=128, tk=384, d=128, causal=False, window=None),
    dict(b=1, hq=8, hkv=2, tq=64, tk=64, d=32, causal=True, window=32),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_flash_attention_sweep(case, dtype, tol):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (case["b"], case["hq"], case["tq"],
                                      case["d"]))).astype(dtype)
    k = jnp.asarray(rng.normal(0, 1, (case["b"], case["hkv"], case["tk"],
                                      case["d"]))).astype(dtype)
    v = jnp.asarray(rng.normal(0, 1, (case["b"], case["hkv"], case["tk"],
                                      case["d"]))).astype(dtype)
    got = flash_attention(q, k, v, causal=case["causal"],
                          window=case["window"], interpret=True)
    want = attention_ref(q, k, v, causal=case["causal"], window=case["window"])
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol, (case, dtype, err)


def test_chunked_attention_matches_ref():
    from repro.models.layers import _chunked_attention
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(0, 1, (2, 4, 4096, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (2, 2, 4096, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (2, 2, 4096, 32)).astype(np.float32))
    for w in (None, 512):
        got = _chunked_attention(q, k, v, window=w)
        want = attention_ref(q, k, v, causal=True, window=w)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5


# --- fused rmsnorm -----------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64), (3, 7, 96), (256, 1152), (1, 8)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)])
def test_rmsnorm_kernel(shape, dtype, tol):
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 2, shape)).astype(dtype)
    s = jnp.asarray(rng.normal(0, 0.1, shape[-1:])).astype(dtype)
    got = rmsnorm(x, s, interpret=True)
    want = rmsnorm_ref(x, s)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol, (shape, dtype, err)


def test_rmsnorm_matches_model_layer():
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.models.layers import rms_norm
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (5, 128)).astype(np.float32))
    s = jnp.asarray(rng.normal(0, 0.1, (128,)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(rmsnorm(x, s, interpret=True)),
                               np.asarray(rms_norm(x, s)), rtol=1e-5, atol=1e-6)
