"""Compile-only checks of the chip's main path for a described TPU v5e.

The Pallas kernels run in interpret mode everywhere else in the suite,
and interpret mode accepts block shapes and in-kernel ops the TPU
compiler (Mosaic) refuses.  Here each kernel, and one whole prefill step
and one whole decode step of a qwen2.5-3b period, is compiled at its
published widths for one chip of a described ``v5e:2x2`` topology — no
chip is needed and nothing runs.  Each test asserts the compiled program
holds a ``tpu_custom_call``, i.e. a kernel really was lowered for the
chip and did not fall back to XLA ops.

The topology is described in a fixture, never while this file is
imported: only one process at a time may load the TPU library, and the
suite runs on several workers.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import fused_decode
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan
from repro.models import lm

QWEN = get_config("qwen2.5-3b")
MAMBA = get_config("mamba2-370m")
# the serving shapes of the chip smoke run: groups of 4 requests, prompts
# bucketed to 512 tokens, 32 new tokens each
B, BUCKET, CAP = 4, 512, 544


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(no_compile_cache):
    """One chip of a described v5e:2x2 host, as a sharding."""
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, *shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_at_qwen_prefill(chip):
    a = QWEN.attn
    txt = _compiled_text(
        functools.partial(flash_attention, causal=True),
        _sds(chip, B, BUCKET, a.n_heads, a.head_dim),
        _sds(chip, B, BUCKET, a.n_kv_heads, a.head_dim),
        _sds(chip, B, BUCKET, a.n_kv_heads, a.head_dim))
    assert "tpu_custom_call" in txt


def test_decode_attention_compiles_at_qwen_cache(chip):
    a = QWEN.attn
    txt = _compiled_text(
        decode_attention,
        _sds(chip, B, a.n_heads, a.head_dim),
        _sds(chip, B, CAP, a.n_kv_heads, a.head_dim),
        _sds(chip, B, CAP, a.n_kv_heads, a.head_dim),
        _sds(chip, dtype=jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("rows", [(B, BUCKET), (B, 1)],
                         ids=["prefill", "decode"])
def test_rmsnorm_compiles_at_qwen_width(chip, rows):
    txt = _compiled_text(
        functools.partial(rmsnorm, eps=QWEN.norm_eps),
        _sds(chip, *rows, QWEN.d_model),
        _sds(chip, QWEN.d_model, dtype=jnp.float32))
    assert "tpu_custom_call" in txt


def test_composed_attn_decode_step_compiles_at_qwen_width(chip):
    """At qwen2.5-3b's widths the sublayer is too large for the single
    fused kernel, so the step composes XLA ops around the
    `decode_attention` kernel."""
    a, d = QWEN.attn, QWEN.d_model
    assert fused_decode.step_path("pallas", d, a.n_heads, a.n_kv_heads,
                                  a.head_dim, CAP) == "composed"
    hq, hkv = a.n_heads * a.head_dim, a.n_kv_heads * a.head_dim
    step = functools.partial(
        fused_decode.attn_decode_step, n_heads=a.n_heads,
        head_dim=a.head_dim, eps=QWEN.norm_eps, rope_theta=a.rope_theta,
        mode="pallas")
    txt = _compiled_text(
        lambda x, kc, vc, pos, w: step(x, kc, vc, pos, **w),
        _sds(chip, B, 1, d),
        _sds(chip, B, CAP, a.n_kv_heads, a.head_dim),
        _sds(chip, B, CAP, a.n_kv_heads, a.head_dim),
        _sds(chip, dtype=jnp.int32),
        {"norm": _sds(chip, d, dtype=jnp.float32),
         "wq": _sds(chip, d, hq), "wk": _sds(chip, d, hkv),
         "wv": _sds(chip, d, hkv), "wo": _sds(chip, hq, d),
         "bq": _sds(chip, hq), "bk": _sds(chip, hkv), "bv": _sds(chip, hkv)})
    assert "tpu_custom_call" in txt


def test_fused_attn_decode_kernel_compiles_where_selected(chip):
    """The single fused kernel, at a width it is selected for (128-wide
    heads, a sublayer that fits VMEM)."""
    d, heads, kv, hd, cap = 512, 4, 2, 128, 256
    assert fused_decode.step_path("pallas", d, heads, kv, hd,
                                  cap) == "fused"
    step = functools.partial(
        fused_decode.attn_decode_step, n_heads=heads, head_dim=hd,
        eps=1e-6, rope_theta=1e6, mode="pallas")
    txt = _compiled_text(
        lambda x, kc, vc, pos, w: step(x, kc, vc, pos, **w),
        _sds(chip, 2, 1, d),
        _sds(chip, 2, cap, kv, hd), _sds(chip, 2, cap, kv, hd),
        _sds(chip, dtype=jnp.int32),
        {"norm": _sds(chip, d, dtype=jnp.float32),
         "wq": _sds(chip, d, heads * hd), "wk": _sds(chip, d, kv * hd),
         "wv": _sds(chip, d, kv * hd), "wo": _sds(chip, heads * hd, d),
         "bq": _sds(chip, heads * hd), "bk": _sds(chip, kv * hd),
         "bv": _sds(chip, kv * hd)})
    assert "tpu_custom_call" in txt


def test_ssd_scan_compiles_at_mamba2_370m_width(chip):
    m = MAMBA.mamba
    H, P, N = m.n_ssm_heads(MAMBA.d_model), m.head_dim, m.d_state
    L = 512
    txt = _compiled_text(
        functools.partial(ssd_scan, chunk=128),
        _sds(chip, 2, L, H, P), _sds(chip, 2, L, H, dtype=jnp.float32),
        _sds(chip, H, dtype=jnp.float32),
        _sds(chip, 2, L, N), _sds(chip, 2, L, N))
    assert "tpu_custom_call" in txt


@pytest.fixture(scope="module")
def qwen_period(chip):
    """Shapes of one qwen2.5-3b period's parameters on the chip, from
    `jax.eval_shape` of the real initialiser (nothing is allocated)."""
    params = jax.eval_shape(lambda k: lm.init_params(QWEN, k),
                            jax.random.PRNGKey(0))
    return _on(chip, jax.eval_shape(lambda l: lm.slice_periods(l, 0, 1),
                                    params["layers"]))


def test_qwen_period_prefill_step_compiles(chip, qwen_period):
    txt = _compiled_text(
        lambda p, x: lm.prefill_blocks(QWEN, p, x, jnp.arange(BUCKET),
                                       cap=CAP, impl="pallas"),
        qwen_period, _sds(chip, B, BUCKET, QWEN.d_model))
    assert "tpu_custom_call" in txt


def test_qwen_period_decode_step_compiles(chip, qwen_period):
    x = _sds(chip, B, BUCKET, QWEN.d_model)
    _, cache = jax.eval_shape(
        lambda p, xx: lm.prefill_blocks(QWEN, p, xx, jnp.arange(BUCKET),
                                        cap=CAP, impl="pallas"),
        qwen_period, x)
    txt = _compiled_text(
        lambda p, c, xx, pos: lm.decode_blocks(QWEN, p, c, xx, pos,
                                               impl="pallas"),
        qwen_period, _on(chip, cache), _sds(chip, B, 1, QWEN.d_model),
        _sds(chip, dtype=jnp.int32))
    assert "tpu_custom_call" in txt
