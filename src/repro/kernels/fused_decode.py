"""Fused single-token attention-sublayer step for decode.

One decode token through an attention sublayer is rmsnorm -> QKV -> rope
-> ring-buffer cache write -> decode attention -> output proj -> residual.
The historical path (`blocks.attn_decode`, kept verbatim under the
``"ref"`` impl) dispatches those as separate XLA ops and rep-folds the
GQA cache; this module fuses them:

  * `_composed_step` — kernel-composed XLA: the same op sequence but with
    the decode attention swapped for `ref.decode_attention_chunked` (the
    no-repeat online-softmax blocking) or the Pallas
    `decode_attention` kernel.  This is the ``"fused"`` CPU hot path and
    the universal fallback.
  * `_fused_pallas_step` — the whole sublayer in ONE Pallas kernel
    (grid over batch rows, scalar-prefetched position): norm, QKV, rope,
    attention with *stale-slot masking*, output proj, residual.  The
    cache write stays OUTSIDE the kernel as a `dynamic_update_slice` so
    XLA's donation aliasing still updates the ring buffer in place —
    pushing the write inside via input/output aliasing would force a
    full-cache copy per token.  Instead the kernel masks the (stale)
    slot about to be overwritten and appends the fresh token's logit as
    an explicit extra column: attention over {old entries != slot} plus
    the current token is exactly attention over the *updated* cache at
    ``cache_len = min(pos+1, C)``, for both the growing (pos < C) and
    wrapped (pos >= C) ring states.

Weight-stationarity note: the fused kernel re-streams the projection
weights once per batch row — the right trade at decode batch sizes,
where the cache and weights dominate bytes anyway; `_fits_vmem` guards
the per-row working set and falls back to `_composed_step` when the
sublayer would not fit.

The rope/rmsnorm math is replicated locally from `models.common`
(kernels must not import models); `tests/test_kernels.py` pins the
step against the historical op-by-op body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .decode_attention import decode_attention

NEG_INF = -1e30

# per-kernel-instance VMEM working-set ceiling for the fully-fused step
# (weights + both cache rows + activations, f32); beyond this we compose
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _rope_tables(pos, d2, theta):
    """cos/sin rows (1, d2) for one absolute position (f32)."""
    # mirrors models.common.rope's frequency layout; 2D int iota for TPU
    exp = jax.lax.broadcasted_iota(jnp.int32, (1, d2), 1).astype(jnp.float32) / d2
    freq = theta ** (-exp)
    ang = pos.astype(jnp.float32) * freq
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rope(x, cos, sin):
    """x: (rows, hd); rotate the first 2*d2 dims, pass the odd tail."""
    d = x.shape[-1]
    d2 = cos.shape[-1]
    x1, x2 = x[:, :d2], x[:, d2:2 * d2]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if 2 * d2 < d:
        rot = jnp.concatenate([rot, x[:, 2 * d2:]], axis=-1)
    return rot


def _rope_host(x, positions, theta):
    """(B, S, heads, hd) rope — local copy of models.common.rope math."""
    d = x.shape[-1]
    d2 = d // 2
    freq = theta ** (-jnp.arange(0, d2, dtype=jnp.float32) / d2)
    ang = positions[..., None].astype(jnp.float32) * freq
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:2 * d2]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if 2 * d2 < d:
        rot = jnp.concatenate([rot, x[..., 2 * d2:]], axis=-1)
    return rot.astype(x.dtype)


def _fused_kernel(pos_ref, x_ref, kc_ref, vc_ref, norm_ref, wq_ref, wk_ref,
                  wv_ref, wo_ref, bq_ref, bk_ref, bv_ref,
                  o_ref, kn_ref, vn_ref, *,
                  n_heads, kv_heads, head_dim, cap, eps, theta, scale,
                  has_bias):
    f32 = jnp.float32
    rep = n_heads // kv_heads
    d2 = head_dim // 2
    pos = pos_ref[0]

    x = x_ref[0].astype(f32)                       # (1, D)
    w = norm_ref[...].astype(f32)                  # (1, D)
    rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    h = x * rms * w                                # (1, D)

    def proj(w_ref, b_ref, rows):
        y = jax.lax.dot_general(
            h, w_ref[...].astype(f32), (((1,), (0,)), ((), ())))
        if has_bias:
            y = y + b_ref[...].astype(f32)
        return y.reshape(rows, head_dim)

    q = proj(wq_ref, bq_ref, n_heads)              # (H, hd)
    k = proj(wk_ref, bk_ref, kv_heads)             # (KV, hd)
    v = proj(wv_ref, bv_ref, kv_heads)             # (KV, hd)

    cos, sin = _rope_tables(pos, d2, theta)
    q = _apply_rope(q, cos, sin) * scale
    k = _apply_rope(k, cos, sin)

    slot = jnp.mod(pos, cap)
    live = jnp.minimum(pos, cap)      # valid OLD entries (slot is stale)
    idx = jax.lax.broadcasted_iota(jnp.int32, (rep, cap), 1)
    mask = (idx < live) & (idx != slot)

    # static loop over KV groups keeps every in-kernel op a 2D matmul /
    # elementwise (no 3D transposes for Mosaic to lower)
    outs = []
    for g in range(kv_heads):
        qg = q[g * rep:(g + 1) * rep]              # (rep, hd)
        kg = kc_ref[0, :, g, :].astype(f32)        # (cap, hd)
        vg = vc_ref[0, :, g, :].astype(f32)
        s = jax.lax.dot_general(qg, kg, (((1,), (1,)), ((), ())))
        s = jnp.where(mask, s, NEG_INF)            # (rep, cap)
        s_cur = jax.lax.dot_general(               # fresh token's column
            qg, k[g:g + 1], (((1,), (1,)), ((), ())))       # (rep, 1)
        m = jnp.maximum(s.max(axis=1, keepdims=True), s_cur)
        p = jnp.exp(s - m)
        p_cur = jnp.exp(s_cur - m)
        l = p.sum(axis=1, keepdims=True) + p_cur
        og = jax.lax.dot_general(p, vg, (((1,), (0,)), ((), ())))
        og = (og + p_cur * v[g:g + 1]) / l         # (rep, hd)
        outs.append(og)
    o = jnp.concatenate(outs, axis=0) if kv_heads > 1 else outs[0]

    orow = jax.lax.dot_general(
        o.reshape(1, n_heads * head_dim), wo_ref[...].astype(f32),
        (((1,), (0,)), ((), ())))
    o_ref[0] = (x + orow).astype(o_ref.dtype)
    kn_ref[0] = k.astype(kn_ref.dtype)
    vn_ref[0] = v.astype(vn_ref.dtype)


def _fits_vmem(d_model, n_heads, kv_heads, head_dim, cap) -> bool:
    """Whether the single fused kernel can take this sublayer: its working
    set fits the VMEM budget, and its head width is whole 128-lane rows
    (the kernel splits each projection row into per-head rows, a reshape
    Mosaic lowers only for lane-aligned head widths)."""
    if head_dim % 128:
        return False
    qkvo = d_model * (2 * n_heads + 2 * kv_heads) * head_dim
    cache = 2 * cap * kv_heads * head_dim
    act = 4 * d_model + 2 * n_heads * head_dim + cap * max(8, n_heads)
    return 4 * (qkvo + cache + act) <= _VMEM_BUDGET_BYTES


def _fused_pallas_step(x2, k_cache, v_cache, pos, *, norm, wq, wk, wv, wo,
                       bq, bk, bv, n_heads, head_dim, eps, theta, scale,
                       interpret):
    B, D = x2.shape
    _, cap, kv_heads, _ = k_cache.shape
    has_bias = bq is not None
    hdim = n_heads * head_dim
    kdim = kv_heads * head_dim
    zb = jnp.zeros((1, 1), x2.dtype)   # bias placeholders keep arity fixed
    biases = ((bq.reshape(1, hdim), bk.reshape(1, kdim),
               bv.reshape(1, kdim)) if has_bias else (zb, zb, zb))
    bspecs = ([pl.BlockSpec((1, hdim), lambda b, _p: (0, 0)),
               pl.BlockSpec((1, kdim), lambda b, _p: (0, 0)),
               pl.BlockSpec((1, kdim), lambda b, _p: (0, 0))] if has_bias
              else [pl.BlockSpec((1, 1), lambda b, _p: (0, 0))] * 3)

    kernel = functools.partial(
        _fused_kernel, n_heads=n_heads, kv_heads=kv_heads,
        head_dim=head_dim, cap=cap, eps=eps, theta=theta, scale=scale,
        has_bias=has_bias)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            # rows ride as (B, 1, D): a (1, D) block of a (B, D) array is
            # not a whole TPU tile, a (1, 1, D) block of (B, 1, D) is
            pl.BlockSpec((1, 1, D), lambda b, _p: (b, 0, 0)),
            pl.BlockSpec((1, cap, kv_heads, head_dim),
                         lambda b, _p: (b, 0, 0, 0)),
            pl.BlockSpec((1, cap, kv_heads, head_dim),
                         lambda b, _p: (b, 0, 0, 0)),
            pl.BlockSpec((1, D), lambda b, _p: (0, 0)),
            pl.BlockSpec((D, hdim), lambda b, _p: (0, 0)),
            pl.BlockSpec((D, kdim), lambda b, _p: (0, 0)),
            pl.BlockSpec((D, kdim), lambda b, _p: (0, 0)),
            pl.BlockSpec((hdim, D), lambda b, _p: (0, 0)),
            *bspecs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, D), lambda b, _p: (b, 0, 0)),
            pl.BlockSpec((1, kv_heads, head_dim), lambda b, _p: (b, 0, 0)),
            pl.BlockSpec((1, kv_heads, head_dim), lambda b, _p: (b, 0, 0)),
        ],
    )
    posv = jnp.asarray(pos, jnp.int32).reshape((1,))
    out, k_new, v_new = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, D), x2.dtype),
            jax.ShapeDtypeStruct((B, kv_heads, head_dim), k_cache.dtype),
            jax.ShapeDtypeStruct((B, kv_heads, head_dim), v_cache.dtype),
        ],
        interpret=interpret,
    )(posv, x2[:, None], k_cache, v_cache, norm.reshape(1, D), wq, wk, wv,
      wo, *biases)
    slot = jnp.mod(pos, cap)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new[:, None], (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new[:, None], (0, slot, 0, 0))
    return out, k_cache, v_cache


def _composed_step(x, k_cache, v_cache, pos, *, norm, wq, wk, wv, wo,
                   bq, bk, bv, n_heads, head_dim, eps, theta, scale,
                   attn_mode, block_k):
    B = x.shape[0]
    cap = k_cache.shape[1]
    kv_heads = wk.shape[1] // head_dim
    h = ref.rmsnorm_reference(x, norm, eps=eps)
    q = h @ wq.astype(x.dtype)
    k = h @ wk.astype(x.dtype)
    v = h @ wv.astype(x.dtype)
    if bq is not None:
        q = q + bq.astype(x.dtype)
        k = k + bk.astype(x.dtype)
        v = v + bv.astype(x.dtype)
    positions = jnp.full((1,), pos)
    q = _rope_host(q.reshape(B, 1, n_heads, head_dim), positions, theta)
    k = _rope_host(k.reshape(B, 1, kv_heads, head_dim), positions, theta)
    v = v.reshape(B, 1, kv_heads, head_dim)
    slot = jnp.mod(pos, cap)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0))
    cache_len = jnp.minimum(pos + 1, cap)
    if attn_mode in ("pallas", "interpret"):
        o = decode_attention(q[:, 0], k_cache, v_cache, cache_len,
                             scale=scale, block_k=block_k,
                             interpret=attn_mode == "interpret")
    else:
        o = ref.decode_attention_chunked(q[:, 0], k_cache, v_cache,
                                         cache_len, scale=scale,
                                         block_k=block_k)
    out = x + o.reshape(B, 1, -1) @ wo.astype(x.dtype)
    return out, k_cache, v_cache


def step_path(mode, d_model, n_heads, kv_heads, head_dim, cap) -> str:
    """The body `attn_decode_step` runs for these shapes: ``"fused"`` (the
    single Pallas kernel) or ``"composed"`` (`_composed_step`, whose
    attention is the `decode_attention` kernel under pallas/interpret)."""
    if mode in ("pallas", "interpret") and _fits_vmem(
            d_model, n_heads, kv_heads, head_dim, cap):
        return "fused"
    return "composed"


def attn_decode_step(x, k_cache, v_cache, pos, *, norm, wq, wk, wv, wo,
                     bq=None, bk=None, bv=None, n_heads, head_dim,
                     eps=1e-5, rope_theta=10_000.0, mode="fused",
                     block_k: int = 128):
    """One-token attention sublayer: (B, 1, D) in, (out, k_cache, v_cache)
    out, ring slot ``pos % C`` freshly written.  Cache outputs keep the
    input avals leaf-for-leaf (the `lm.decode_cache_structs` donation
    contract).  ``mode``: "pallas"/"interpret" try the single fused
    Pallas kernel (VMEM permitting) and fall back to the kernel-composed
    step; "fused" (CPU default) composes around the chunked no-repeat
    attention; "ref" is handled by `blocks.attn_decode` upstream and
    never reaches here.
    """
    B, _, D = x.shape
    cap, kv_heads = k_cache.shape[1], k_cache.shape[2]
    scale = head_dim ** -0.5
    if step_path(mode, D, n_heads, kv_heads, head_dim, cap) == "fused":
        return _fused_pallas_step(
            x[:, 0], k_cache, v_cache, pos, norm=norm, wq=wq, wk=wk, wv=wv,
            wo=wo, bq=bq, bk=bk, bv=bv, n_heads=n_heads, head_dim=head_dim,
            eps=eps, theta=rope_theta, scale=scale,
            interpret=mode == "interpret")
    return _composed_step(
        x, k_cache, v_cache, pos, norm=norm, wq=wq, wk=wk, wv=wv, wo=wo,
        bq=bq, bk=bk, bv=bv, n_heads=n_heads, head_dim=head_dim, eps=eps,
        theta=rope_theta, scale=scale, attn_mode=mode, block_k=block_k)
