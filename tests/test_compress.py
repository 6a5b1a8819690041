"""Gradient compression: quantization contracts, ring correctness (8 fake
devices via subprocess), error-feedback convergence."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.optim.compress import (dequantize_int8, ef_compress,
                                  quantize_int8)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_quantize_roundtrip_error_bound(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (257,)) * 10
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) / 2 + 1e-6   # half-ULP of the scale


def test_ef_contract_exact():
    """dequant(q) + new_err == x + err, exactly (in f32)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64,))
    err = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.01
    (q, s), new_err = ef_compress(x, err)
    lhs = dequantize_int8(q, s) + new_err
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(x + err),
                               rtol=0, atol=1e-6)


def test_ef_sgd_converges_like_uncompressed():
    """Toy quadratic: EF-compressed gradient steps reach the optimum."""
    A = jnp.diag(jnp.linspace(0.5, 3.0, 16))
    b = jnp.arange(16.0) / 8

    def grad(w):
        return A @ w - b

    w_ref = jnp.zeros(16)
    w_c = jnp.zeros(16)
    err = jnp.zeros(16)
    for _ in range(300):
        w_ref = w_ref - 0.1 * grad(w_ref)
        (q, s), err = ef_compress(grad(w_c), err)
        w_c = w_c - 0.1 * dequantize_int8(q, s)
    opt = jnp.linalg.solve(A, b)
    assert float(jnp.linalg.norm(w_ref - opt)) < 1e-3
    assert float(jnp.linalg.norm(w_c - opt)) < 1e-2   # EF keeps convergence


_RING_CHECK = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    import sys
    sys.path.insert(0, "src")
    from repro.launch.mesh import auto_mesh
    from repro.optim.compress import (CompressionState, compressed_mean,
                                      make_compressed_sync)

    mesh = auto_mesh((8,), ("data",))
    n = 8
    rng = np.random.default_rng(0)
    local = rng.normal(size=(8, 4096)).astype(np.float32)

    # 1. raw ring mean vs exact
    def body(x):
        return compressed_mean(x[0], "data", n)[None]
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_vma=False))
    got = np.asarray(f(local))
    want = local.mean(axis=0)
    for r in range(8):
        err = np.abs(got[r] - want)
        # two quantization stages; scale ~ max|x|/127
        assert err.max() < 0.15, err.max()

    # 2. EF sync: averaged over steps, the quantization error vanishes
    sync = make_compressed_sync(mesh, "data")
    g = {"w": jnp.asarray(local)}
    st = CompressionState.init({"w": jnp.zeros(4096)}, 8)
    acc = np.zeros(4096)
    steps = 30
    for i in range(steps):
        synced, st = sync(g, st)
        acc += np.asarray(synced["w"][0])
    drift = np.abs(acc / steps - want).max()
    assert drift < 0.02, drift          # EF removes the bias
    print("RING_OK", err.max(), drift)
""")


def test_ring_mean_and_ef_sync_8dev():
    """Run the ring on 8 simulated devices in a subprocess (device count
    must be set before jax initialises)."""
    r = subprocess.run([sys.executable, "-c", _RING_CHECK],
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "RING_OK" in r.stdout
