"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


ATTN_SHAPES = [
    # (B, Sq, Sk, H, KV, D, block_q, block_k)
    (1, 16, 16, 2, 2, 16, 8, 8),       # MHA, tiny
    (2, 64, 64, 4, 2, 32, 16, 16),     # GQA 2:1
    (1, 33, 33, 8, 1, 64, 16, 16),     # MQA, ragged seq
    (2, 32, 128, 4, 4, 32, 16, 32),    # cross/prefix (Sk > Sq)
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(shape, dtype, causal):
    b, sq, sk, h, kv, d, bq, bk = shape
    rng = np.random.default_rng(hash((shape, causal)) % 2**31)
    q = _rand(rng, (b, sq, h, d), dtype)
    k = _rand(rng, (b, sk, kv, d), dtype)
    v = _rand(rng, (b, sk, kv, d), dtype)
    off = sk - sq
    got = flash_attention(q, k, v, causal=causal, kv_offset=off,
                          block_q=bq, block_k=bk, interpret=True)
    want = ref.mha_reference(q, k, v, causal=causal, kv_offset=off)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [1, 7, 16, 64])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(7)
    q = _rand(rng, (2, 48, 4, 32), jnp.float32)
    k = _rand(rng, (2, 48, 2, 32), jnp.float32)
    v = _rand(rng, (2, 48, 2, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=16, block_k=16, interpret=True)
    want = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_attention_block_sparsity_skips_are_correct():
    """Causal + window => many fully-masked blocks; results must not change."""
    rng = np.random.default_rng(8)
    q = _rand(rng, (1, 256, 2, 16), jnp.float32)
    k = _rand(rng, (1, 256, 2, 16), jnp.float32)
    v = _rand(rng, (1, 256, 2, 16), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=32,
                          block_q=32, block_k=32, interpret=True)
    want = ref.mha_reference(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


SSD_SHAPES = [
    # (B, L, H, P, N, chunk)
    (1, 16, 1, 4, 8, 4),
    (2, 64, 3, 8, 16, 16),
    (1, 50, 2, 16, 32, 16),   # ragged
    (2, 128, 4, 64, 128, 32),  # production-like dims
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_oracle(shape, dtype):
    b, l, h, p, n, chunk = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    x = _rand(rng, (b, l, h, p), dtype)
    dt = jnp.asarray(rng.uniform(0.05, 0.8, size=(b, l, h)), dtype)
    a = jnp.asarray(-rng.uniform(0.5, 1.5, size=(h,)), jnp.float32)
    bb = _rand(rng, (b, l, n), dtype)
    cc = _rand(rng, (b, l, n), dtype)
    got_y, got_s = ssd_scan(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    want_y, want_s = ref.ssd_reference(x, dt, a, bb, cc)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_y, np.float32),
                               np.asarray(want_y, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), **tol)


def test_ssd_chunked_ref_matches_sequential():
    rng = np.random.default_rng(3)
    x = _rand(rng, (2, 37, 3, 8), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.8, size=(2, 37, 3)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 1.5, size=(3,)), jnp.float32)
    b = _rand(rng, (2, 37, 16), jnp.float32)
    c = _rand(rng, (2, 37, 16), jnp.float32)
    y1, s1 = ref.ssd_reference(x, dt, a, b, c)
    y2, s2 = ref.ssd_chunked(x, dt, a, b, c, chunk=8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5, rtol=1e-5)


def test_ssd_decode_step_consistent_with_scan():
    rng = np.random.default_rng(4)
    B, L, H, P, N = 1, 12, 2, 4, 8
    x = _rand(rng, (B, L, H, P), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.8, size=(B, L, H)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 1.5, size=(H,)), jnp.float32)
    b = _rand(rng, (B, L, N), jnp.float32)
    c = _rand(rng, (B, L, N), jnp.float32)
    want_y, want_s = ref.ssd_reference(x, dt, a, b, c)
    s = jnp.zeros((B, H, P, N), jnp.float32)
    for t in range(L):
        y, s = ref.ssd_decode_step(s, x[:, t], dt[:, t], a, b[:, t], c[:, t])
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y[:, -1]), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape", [(4, 16), (3, 5, 64), (2, 7, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_oracle(shape, dtype):
    rng = np.random.default_rng(5)
    x = _rand(rng, shape, dtype)
    w = _rand(rng, shape[-1:], jnp.float32)
    got = rmsnorm(x, w, block_rows=2, interpret=True)
    want = ref.rmsnorm_reference(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_ops_dispatch_fused_on_cpu():
    assert ops.resolve_impl(None) == "fused"
    assert ops.resolve_impl("interpret") == "interpret"
    assert ops.resolve_impl("ref") == "ref"
    rng = np.random.default_rng(6)
    q = _rand(rng, (1, 8, 2, 16), jnp.float32)
    k = _rand(rng, (1, 8, 2, 16), jnp.float32)
    v = _rand(rng, (1, 8, 2, 16), jnp.float32)
    a = ops.attention(q, k, v)          # fused == ref for prefill wrappers
    b = ops.attention(q, k, v, impl="interpret", block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)
    c = ops.attention(q, k, v, impl="ref")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_ops_dispatch_honors_env_var(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    assert ops.resolve_impl(None) == "interpret"
    # explicit per-call / set_default_impl still win over the env var
    assert ops.resolve_impl("ref") == "ref"
    ops.set_default_impl("fused")
    try:
        assert ops.resolve_impl(None) == "fused"
    finally:
        ops.set_default_impl(None)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "bogus")
    assert ops.resolve_impl(None) == "fused"   # unknown names fall to auto


# ===========================================================================
# decode attention (single token over a ring-buffered cache)
# ===========================================================================
DECODE_SHAPES = [
    # (B, H, KV, hd, C, cache_len, window)
    (1, 8, 8, 16, 32, 32, None),    # MHA, full cache
    (2, 8, 4, 32, 64, 17, None),    # GQA 2:1, short prefix masking
    (3, 8, 1, 32, 48, 5, None),     # MQA
    (2, 16, 2, 16, 200, 77, None),  # GQA 8:1, multi-block (block_k=64)
    (2, 8, 4, 32, 64, 64, 30),      # SWA window inside a full ring
    (1, 6, 2, 20, 130, 100, None),  # odd head count / head dim tail
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_oracle(shape, dtype):
    from repro.kernels.decode_attention import decode_attention
    b, h, kv, hd, c, clen, window = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    q = _rand(rng, (b, h, hd), dtype)
    k = _rand(rng, (b, c, kv, hd), dtype)
    v = _rand(rng, (b, c, kv, hd), dtype)
    want = ref.decode_attention_ref(q, k, v, clen, window=window)
    got_k = decode_attention(q, k, v, clen, window=window, block_k=64,
                             interpret=True)
    got_c = ref.decode_attention_chunked(q, k, v, clen, window=window,
                                         block_k=64)
    np.testing.assert_allclose(np.asarray(got_k, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(got_c, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_chunked_per_batch_lengths():
    rng = np.random.default_rng(11)
    q = _rand(rng, (3, 8, 32), jnp.float32)
    k = _rand(rng, (3, 40, 4, 32), jnp.float32)
    v = _rand(rng, (3, 40, 4, 32), jnp.float32)
    lens = jnp.asarray([1, 17, 40])
    want = ref.decode_attention_ref(q, k, v, lens[:, None])
    got = ref.decode_attention_chunked(q, k, v, lens, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # the ops wrapper must not hand per-batch lengths to the Pallas kernel
    via_ops = ops.decode_attention(q, k, v, lens, impl="interpret")
    np.testing.assert_allclose(np.asarray(via_ops), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_ring_wraparound():
    """pos > C: every slot is live; kernel == oracle on the wrapped ring."""
    from repro.kernels.decode_attention import decode_attention
    rng = np.random.default_rng(12)
    B, H, KV, hd, C = 2, 8, 4, 32, 24
    q = _rand(rng, (B, H, hd), jnp.float32)
    k = _rand(rng, (B, C, KV, hd), jnp.float32)
    v = _rand(rng, (B, C, KV, hd), jnp.float32)
    for pos in [C, C + 1, 5 * C + 3]:
        clen = min(pos + 1, C)              # what blocks.attn_decode passes
        want = ref.decode_attention_ref(q, k, v, clen)
        got = decode_attention(q, k, v, clen, block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_decode_attention_property_bcpos():
    """hypothesis sweep over (B, C, pos): kernel blocking == oracle for any
    ring state, including cache_len < C masking and wrapped positions."""
    from hypothesis import given, settings, strategies as st
    from repro.kernels.decode_attention import decode_attention

    @settings(max_examples=20, deadline=None)
    @given(b=st.integers(1, 3), c=st.integers(1, 70),
           pos=st.integers(0, 200), block=st.sampled_from([8, 32, 128]))
    def prop(b, c, pos, block):
        rng = np.random.default_rng(b * 1000003 + c * 101 + pos)
        H, KV, hd = 4, 2, 16
        q = _rand(rng, (b, H, hd), jnp.float32)
        k = _rand(rng, (b, c, KV, hd), jnp.float32)
        v = _rand(rng, (b, c, KV, hd), jnp.float32)
        clen = min(pos + 1, c)
        want = ref.decode_attention_ref(q, k, v, clen)
        got = decode_attention(q, k, v, clen, block_k=block, interpret=True)
        chk = ref.decode_attention_chunked(q, k, v, clen, block_k=block)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(chk), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    prop()


@pytest.mark.parametrize("impl", ["fused", "interpret"])
@pytest.mark.parametrize("pos", [0, 3, 15, 16, 40])
def test_attn_decode_step_matches_historical_body(impl, pos):
    """The fused single-token step (composed XLA and single-Pallas-kernel)
    == the historical op-by-op `blocks.attn_decode` body, across growing
    (pos < C), boundary (pos == C) and wrapped (pos > C) ring states —
    outputs AND the freshly written cache slot."""
    from repro.configs import get_config
    from repro.models import blocks
    from repro.models.common import KeyGen

    cfg = get_config("tiny")
    p = blocks.init_attn(KeyGen(jax.random.PRNGKey(0)), cfg, "t")
    rng = np.random.default_rng(13)
    B, C = 3, 16
    cache = blocks.init_attn_cache(cfg, B, C, jnp.float32)
    cache = {k: _rand(rng, v.shape, jnp.float32) * 0.1
             for k, v in cache.items()}
    x = _rand(rng, (B, 1, cfg.d_model), jnp.float32)
    o_ref, c_ref = blocks.attn_decode(p, cfg, x, cache, jnp.int32(pos),
                                      impl="ref")
    o, c = blocks.attn_decode(p, cfg, x, cache, jnp.int32(pos), impl=impl)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(np.asarray(c[leaf]),
                                   np.asarray(c_ref[leaf]),
                                   atol=5e-5, rtol=5e-5)
        assert c[leaf].shape == c_ref[leaf].shape
        assert c[leaf].dtype == c_ref[leaf].dtype


@pytest.mark.parametrize("pos", [0, 15, 40])
def test_fused_kernel_step_matches_historical_body(pos):
    """At a lane-aligned head width (128) the step takes the single fused
    Pallas kernel, which equals the op-by-op `"ref"` body (outputs and
    the written cache slot) in growing, boundary and wrapped ring
    states."""
    import dataclasses
    from repro.configs import get_config
    from repro.kernels import fused_decode
    from repro.models import blocks
    from repro.models.common import KeyGen

    tiny = get_config("tiny")
    cfg = dataclasses.replace(tiny, attn=dataclasses.replace(
        tiny.attn, n_heads=4, n_kv_heads=2, head_dim=128, qkv_bias=True))
    a = cfg.attn
    B, C = 2, 16
    assert fused_decode.step_path("interpret", cfg.d_model, a.n_heads,
                                  a.n_kv_heads, a.head_dim, C) == "fused"
    p = blocks.init_attn(KeyGen(jax.random.PRNGKey(0)), cfg, "t")
    rng = np.random.default_rng(17)
    p = {k: (_rand(rng, v.shape, jnp.float32) * 0.1 if k.startswith("b")
             else v) for k, v in p.items()}
    cache = {k: _rand(rng, v.shape, jnp.float32) * 0.1 for k, v in
             blocks.init_attn_cache(cfg, B, C, jnp.float32).items()}
    x = _rand(rng, (B, 1, cfg.d_model), jnp.float32)
    o_ref, c_ref = blocks.attn_decode(p, cfg, x, cache, jnp.int32(pos),
                                      impl="ref")
    o, c = blocks.attn_decode(p, cfg, x, cache, jnp.int32(pos),
                              impl="interpret")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-5)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(np.asarray(c[leaf]),
                                   np.asarray(c_ref[leaf]),
                                   atol=5e-5, rtol=5e-5)


def test_cross_attn_decode_dispatches_like_self_attn():
    from repro.configs import get_config
    from repro.models import blocks
    from repro.models.common import KeyGen

    cfg = get_config("tiny")
    a = cfg.attn
    p = blocks.init_attn(KeyGen(jax.random.PRNGKey(1)), cfg, "t")
    rng = np.random.default_rng(14)
    B = 2
    x = _rand(rng, (B, 1, cfg.d_model), jnp.float32)
    enc = (_rand(rng, (B, 7, a.n_kv_heads, a.head_dim), jnp.float32),
           _rand(rng, (B, 7, a.n_kv_heads, a.head_dim), jnp.float32))
    want = blocks.cross_attn_decode(p, cfg, x, enc, impl="ref")
    for impl in ("fused", "interpret"):
        got = blocks.cross_attn_decode(p, cfg, x, enc, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)
