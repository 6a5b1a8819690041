"""Model assembly: decoder-only LM and encoder-decoder, over block patterns.

Structure: layers are grouped into *periods* (one cycle of
``cfg.block_pattern``); parameters of each pattern position are stacked over
periods and the stack is traversed with ``jax.lax.scan`` (O(1) HLO in depth)
with optional rematerialisation — both essential for compiling 60+-layer
configs AOT on 512 partitions.

Public API (all pure functions over plain-dict pytrees):
    m = build_model(cfg)
    params = m.init(rng)
    loss, metrics = m.loss_fn(params, batch)
    logits, cache = m.prefill(params, batch)          # serving: prompt pass
    logits, cache = m.decode_step(params, cache, tokens)
    cache = m.init_cache(batch, capacity, dtype)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import KeyGen, chunked_lm_loss, dense_init, dtype_of, rmsnorm, rope
from . import blocks
from .. import sharding_ctx as sc


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "full": save only period boundaries


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable
    forward: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable


# ===========================================================================
# parameter init
# ===========================================================================
def _init_period(kg: KeyGen, cfg: ModelConfig, tag: str, with_cross: bool):
    period = {}
    for i, (mixer, mlp) in enumerate(cfg.block_pattern):
        pos = {}
        if mixer == "attn":
            pos["mixer"] = blocks.init_attn(kg, cfg, f"{tag}.p{i}.attn")
        else:
            pos["mixer"] = blocks.init_mamba(kg, cfg, f"{tag}.p{i}.mamba")
        if with_cross:
            pos["cross"] = blocks.init_attn(kg, cfg, f"{tag}.p{i}.cross")
        if mlp == "moe":
            pos["mlp"] = blocks.init_moe(kg, cfg, f"{tag}.p{i}.moe")
        else:
            pos["mlp"] = blocks.init_mlp(kg, cfg, f"{tag}.p{i}.mlp")
        period[f"pos{i}"] = pos
    return period


@functools.partial(jax.jit, donate_argnums=0)
def _put_period(stack, j, tree):
    return jax.tree.map(lambda s, leaf: s.at[j].set(leaf), stack, tree)


def _stack_periods(init_one: Callable, n: int):
    """Initialise n periods into leaves stacked along axis 0.  Each
    period is written into its row of the stack in place (the stack is
    donated), so at most one period is held twice — stacking a list of
    finished periods would hold every layer twice at its peak."""
    tree = init_one(0)
    stack = jax.tree.map(lambda leaf: jnp.zeros((n, *leaf.shape), leaf.dtype),
                         tree)
    for j in range(n):
        stack = _put_period(stack, j, tree if j == 0 else init_one(j))
    return stack


def init_params(cfg: ModelConfig, rng) -> dict:
    kg = KeyGen(rng)
    dt = dtype_of(cfg.param_dtype)
    d, vp = cfg.d_model, cfg.padded_vocab
    params: dict[str, Any] = {
        "embed": dense_init(kg("embed"), (vp, d), dt, scale=0.02),
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(kg("head"), (d, vp), dt)
    params["layers"] = _stack_periods(
        lambda j: _init_period(KeyGen(kg("layers", j)), cfg, f"l{j}",
                               with_cross=cfg.encdec),
        cfg.n_periods)
    if cfg.encdec:
        assert cfg.enc_layers % len(cfg.block_pattern) == 0
        n_enc = cfg.enc_layers // len(cfg.block_pattern)
        params["enc_layers"] = _stack_periods(
            lambda j: _init_period(KeyGen(kg("enc_layers", j)), cfg, f"e{j}",
                                   with_cross=False),
            n_enc)
        params["enc_norm"] = jnp.ones((d,), jnp.float32)
    return params


# ===========================================================================
# forward passes
# ===========================================================================
def _apply_period(cfg: ModelConfig, period_params, x, positions, *,
                  causal: bool, enc_out=None, impl=None):
    for i, (mixer, mlp) in enumerate(cfg.block_pattern):
        pp = period_params[f"pos{i}"]
        if mixer == "attn":
            x = blocks.attn_forward(pp["mixer"], cfg, x, positions,
                                    causal=causal, impl=impl)
        else:
            x = blocks.mamba_forward(pp["mixer"], cfg, x, impl=impl)
        if enc_out is not None:
            kv = blocks.cross_kv(pp["cross"], cfg, enc_out)
            x = blocks.cross_attn_forward(pp["cross"], cfg, x, kv, impl=impl)
        if mlp == "moe":
            x = blocks.moe_forward(pp["mlp"], cfg, x)
        else:
            x = blocks.mlp_forward(pp["mlp"], cfg, x)
    return x


def _run_stack(cfg: ModelConfig, stacked, x, positions, *, causal: bool,
               enc_out=None, impl=None, remat: str | None = None):
    def body(h, period_params):
        h = _apply_period(cfg, period_params, h, positions,
                          causal=causal, enc_out=enc_out, impl=impl)
        return h, None

    body = _remat(body, remat if remat is not None else cfg.remat)
    x, _ = jax.lax.scan(body, x, stacked)
    return x


def _embed_inputs(cfg: ModelConfig, params, batch, compute_dt):
    """Token embeddings (+ optional multimodal prefix)."""
    tok = batch["tokens"]
    x = sc.act(jnp.take(params["embed"], tok, axis=0).astype(compute_dt),
               "dp", "sp", None)
    n_prefix = 0
    if cfg.frontend == "vit_stub" and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(compute_dt)
        x = jnp.concatenate([pre, x], axis=1)
        n_prefix = pre.shape[1]
    return x, n_prefix


def forward(cfg: ModelConfig, params, batch, *, impl=None, last_only=False,
            remat: str | None = None):
    """Full-sequence forward.  Returns hidden states (B, S, D) (post-norm)
    and the prefix length that was prepended."""
    compute_dt = dtype_of(cfg.compute_dtype)
    enc_out = None
    if cfg.encdec:
        frames = sc.act(batch["frames"].astype(compute_dt), "dp", "sp", None)
        pos_e = jnp.arange(frames.shape[1])
        enc = _run_stack(cfg, params["enc_layers"], frames, pos_e,
                         causal=False, impl=impl, remat=remat)
        enc_out = rmsnorm(enc, params["enc_norm"], cfg.norm_eps)
    x, n_prefix = _embed_inputs(cfg, params, batch, compute_dt)
    positions = jnp.arange(x.shape[1])
    x = _run_stack(cfg, params["layers"], x, positions, causal=True,
                   enc_out=enc_out, impl=impl, remat=remat)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, n_prefix


def _head(cfg: ModelConfig, params):
    return (params["embed"].T if cfg.tie_embeddings else params["head"])


def loss_fn(cfg: ModelConfig, params, batch, *, impl=None):
    x, n_prefix = forward(cfg, params, batch, impl=impl)
    if n_prefix:
        x = x[:, n_prefix:]
    labels = batch["labels"]
    mask = batch.get("mask")
    loss = chunked_lm_loss(x, _head(cfg, params), labels, mask)
    return loss, {"loss": loss}


def logits_fn(cfg: ModelConfig, params, batch, *, impl=None, last_only=True):
    x, n_prefix = forward(cfg, params, batch, impl=impl, remat="none")
    h = x[:, -1:] if last_only else x
    return h @ _head(cfg, params).astype(x.dtype)


# ===========================================================================
# serving: prefill + decode
# ===========================================================================
def init_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=jnp.bfloat16,
               enc_len: int | None = None):
    cap = blocks.attn_cache_capacity(cfg, capacity)

    def one_period(_):
        period = {}
        for i, (mixer, _) in enumerate(cfg.block_pattern):
            if mixer == "attn":
                c = blocks.init_attn_cache(cfg, batch, cap, dtype)
            else:
                c = blocks.init_mamba_cache(cfg, batch, dtype)
            if cfg.encdec:
                a = cfg.attn
                se = enc_len or cfg.num_prefix
                c = {"self": c,
                     "cross_k": jnp.zeros((batch, se, a.n_kv_heads, a.head_dim), dtype),
                     "cross_v": jnp.zeros((batch, se, a.n_kv_heads, a.head_dim), dtype)}
            period[f"pos{i}"] = c
        return period

    caches = _stack_periods(one_period, cfg.n_periods)
    return {"pos": jnp.zeros((), jnp.int32), "layers": caches}


def slice_periods(stacked, lo: int, hi: int):
    """Periods [lo, hi) of a stacked-period pytree (params or caches).

    The per-stage cache-plumbing primitive: a pipeline stage that owns a
    contiguous run of periods slices its parameters *and* its KV/SSM
    cache out of the stacked representation with the same arithmetic, so
    `prefill_blocks`/`decode_blocks` run unchanged over the sub-stack —
    the staged computation is the same scan body the whole-model path
    compiles, just over fewer periods.  The slice is a copy; a stage that
    shares the device of the whole stack passes ``periods=`` instead."""
    return jax.tree.map(lambda leaf: leaf[lo:hi], stacked)


def _scan_periods(body, x, stacked_params, periods, *xs):
    """``lax.scan`` of ``body(h, (period_params, *xs))`` over the periods.

    ``periods`` None scans every period of ``stacked_params``; an int32
    index vector scans those periods only, read from the whole stack
    inside the loop, so a stage that owns periods [lo, hi) of a stack it
    shares with other stages needs no copy of its slice."""
    if periods is None:
        return jax.lax.scan(body, x, (stacked_params, *xs))

    def indexed(h, idx_xs):
        i, *rest = idx_xs
        pp = jax.tree.map(
            lambda leaf: jax.lax.dynamic_index_in_dim(leaf, i, keepdims=False),
            stacked_params)
        return body(h, (pp, *rest))
    return jax.lax.scan(indexed, x, (periods, *xs))


def prefill_blocks(cfg: ModelConfig, stacked_params, x, positions, *,
                   cap: int, enc_out=None, impl=None, periods=None):
    """Prompt pass over a (sub-)stack of periods: scan the prefill body
    (attention/mamba with cache construction) over ``stacked_params``
    (or over its ``periods``, an int32 index vector — see
    `_scan_periods`).  Returns (hidden, stacked per-period caches).  The
    whole-model `prefill` is embed -> this over ``params["layers"]`` ->
    norm/head; a pipeline block stage is this over its own periods."""
    B, S, _ = x.shape

    def body(h, xs):
        (period_params,) = xs
        period_cache = {}
        for i, (mixer, mlp) in enumerate(cfg.block_pattern):
            pp = period_params[f"pos{i}"]
            if mixer == "attn":
                h2 = rmsnorm(h, pp["mixer"]["norm"], cfg.norm_eps)
                q, k, v = blocks._qkv(pp["mixer"], cfg, h2, positions)
                from ..kernels import ops
                o = ops.attention(q, k, v, causal=True, window=cfg.attn.window,
                                  impl=impl)
                h = h + o.reshape(B, S, -1) @ pp["mixer"]["wo"].astype(h.dtype)
                # ring-layout: position p lands in slot p % cap
                if S >= cap:
                    shift = (S - cap) % cap
                    c = {"k": jnp.roll(k[:, -cap:], shift, axis=1),
                         "v": jnp.roll(v[:, -cap:], shift, axis=1)}
                else:
                    pad = ((0, 0), (0, cap - S), (0, 0), (0, 0))
                    c = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
                c = {"k": sc.act(c["k"], "dp", None, "tp", None),
                     "v": sc.act(c["v"], "dp", None, "tp", None)}
            else:
                m = cfg.mamba
                h2 = rmsnorm(h, pp["mixer"]["norm"], cfg.norm_eps)
                x_in, z, bb, cc, dt = blocks._mamba_proj(pp["mixer"], cfg, h2)
                w = pp["mixer"]["conv_w"].astype(x_in.dtype)
                conv = jnp.zeros_like(x_in)
                for kk in range(m.d_conv):
                    sh = m.d_conv - 1 - kk
                    sl = x_in if sh == 0 else jnp.pad(
                        x_in, ((0, 0), (sh, 0), (0, 0)))[:, :S]
                    conv = conv + sl * w[kk]
                H = m.n_ssm_heads(cfg.d_model)
                xh = jax.nn.silu(conv).reshape(B, S, H, m.head_dim)
                a = -jnp.exp(pp["mixer"]["a_log"])
                from ..kernels import ops
                y, ssm_state = ops.ssd(xh, dt, a, bb, cc, impl=impl)
                y = y + xh * pp["mixer"]["d_skip"][None, None, :, None].astype(xh.dtype)
                y = y.reshape(B, S, -1) * jax.nn.silu(z)
                y = rmsnorm(y, pp["mixer"]["gate_norm"], cfg.norm_eps)
                h = h + y @ pp["mixer"]["w_out"].astype(h.dtype)
                c = {"conv": x_in[:, S - (m.d_conv - 1):].astype(h.dtype),
                     "ssm": sc.act(ssm_state, "dp", "tp", None, None)}
            if cfg.encdec:
                ck, cv = blocks.cross_kv(pp["cross"], cfg, enc_out)
                h = blocks.cross_attn_forward(pp["cross"], cfg, h, (ck, cv),
                                              impl=impl)
                c = {"self": c, "cross_k": ck.astype(h.dtype),
                     "cross_v": cv.astype(h.dtype)}
            if mlp == "moe":
                h = blocks.moe_forward(pp["mlp"], cfg, h)
            else:
                h = blocks.mlp_forward(pp["mlp"], cfg, h)
            period_cache[f"pos{i}"] = c
        return h, period_cache

    return _scan_periods(body, x, stacked_params, periods)


def prefill(cfg: ModelConfig, params, batch, *, capacity: int | None = None,
            impl=None):
    """Prompt pass: returns last-token logits + a decode-ready cache.

    ``capacity``: total cache length to allocate (prompt + tokens still to
    be generated); defaults to the prompt length (no headroom).  SWA archs
    cap it at the attention window (ring buffer)."""
    compute_dt = dtype_of(cfg.compute_dtype)
    enc_out = None
    if cfg.encdec:
        frames = sc.act(batch["frames"].astype(compute_dt), "dp", "sp", None)
        pos_e = jnp.arange(frames.shape[1])
        enc = _run_stack(cfg, params["enc_layers"], frames, pos_e,
                         causal=False, impl=impl, remat="none")
        enc_out = rmsnorm(enc, params["enc_norm"], cfg.norm_eps)
    x, n_prefix = _embed_inputs(cfg, params, batch, compute_dt)
    S = x.shape[1]
    positions = jnp.arange(S)
    cap = blocks.attn_cache_capacity(cfg, capacity or S)
    x, caches = prefill_blocks(cfg, params["layers"], x, positions, cap=cap,
                               enc_out=enc_out, impl=impl)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x[:, -1:] @ _head(cfg, params).astype(x.dtype)
    return logits, {"pos": jnp.asarray(S, jnp.int32), "layers": caches}


def decode_blocks(cfg: ModelConfig, stacked_params, stacked_cache, x, pos, *,
                  impl=None, periods=None):
    """One decode step over a (sub-)stack of periods: scan the decode body
    over (params, cache) period pairs (``periods`` as in
    `prefill_blocks`).  Returns (hidden, new caches).
    The whole-model `decode_step` is embed -> this -> norm/head; a
    pipeline block stage runs it over its resident cache slice.

    ``impl`` threads straight to `kernels.ops` dispatch: every impl
    except ``"ref"`` runs attention blocks through the fused decode step
    (`kernels.fused_decode.attn_decode_step` — one rmsnorm+QKV+rope+
    attention+residual call per block instead of the op-by-op chain);
    ``"ref"`` keeps the historical body, the bitwise oracle for parity
    tests.  None resolves via `REPRO_KERNEL_IMPL` / platform default.

    **Donation-safe cache signature**: the returned cache pytree matches
    ``stacked_cache`` leaf for leaf — same structure, shapes, and dtypes
    (cache writes `.astype` back to the stored dtype; the SSM state stays
    float32) — so an executor compiling this step with the cache donated
    (``donate_argnums``) aliases EVERY leaf onto the resident buffers:
    zero new cache allocations per token.  `decode_cache_structs` is the
    checkable form of this contract."""
    def body(h, xs):
        period_params, period_cache = xs
        new_cache = {}
        for i, (mixer, mlp) in enumerate(cfg.block_pattern):
            pp = period_params[f"pos{i}"]
            pc = period_cache[f"pos{i}"]
            self_c = pc["self"] if cfg.encdec else pc
            if mixer == "attn":
                h, c = blocks.attn_decode(pp["mixer"], cfg, h, self_c, pos,
                                          impl=impl)
            else:
                h, c = blocks.mamba_decode(pp["mixer"], cfg, h, self_c,
                                           impl=impl)
            if cfg.encdec:
                h = blocks.cross_attn_decode(
                    pp["cross"], cfg, h, (pc["cross_k"], pc["cross_v"]),
                    impl=impl)
                c = {"self": c, "cross_k": pc["cross_k"],
                     "cross_v": pc["cross_v"]}
            if mlp == "moe":
                h = blocks.moe_decode(pp["mlp"], cfg, h)
            else:
                h = blocks.mlp_forward(pp["mlp"], cfg, h)
            new_cache[f"pos{i}"] = c
        return h, new_cache

    return _scan_periods(body, x, stacked_params, periods, stacked_cache)


def decode_cache_structs(cfg: ModelConfig, stacked_params, batch: int,
                         prompt: int, cap: int):
    """(cache-in, cache-out) avals of one `decode_blocks` step over a
    (sub-)stack — the donation contract as data: the two pytrees must be
    identical leaf for leaf (structure, shape, dtype) or a donated decode
    step silently falls back to allocating the mismatched leaves.
    Executors precompile against these structs; tests assert equality."""
    dt = dtype_of(cfg.compute_dtype)
    d = cfg.d_model
    x = jax.ShapeDtypeStruct((batch, prompt, d), dt)
    _, cache_in = jax.eval_shape(
        lambda p, xx: prefill_blocks(cfg, p, xx, jnp.arange(prompt), cap=cap),
        stacked_params, x)
    _, cache_out = jax.eval_shape(
        lambda p, c, xx, pp: decode_blocks(cfg, p, c, xx, pp),
        stacked_params, cache_in,
        jax.ShapeDtypeStruct((batch, 1, d), dt),
        jax.ShapeDtypeStruct((), jnp.int32))
    return cache_in, cache_out


def decode_step(cfg: ModelConfig, params, cache, tokens, *, impl=None):
    """One token for every sequence in the batch.  tokens: (B, 1) int32.

    Donation-safe like `decode_blocks`: the returned cache (including the
    ``pos`` scalar, which aliases onto ``pos + 1``) matches the input
    cache aval for aval, so single-device servers may donate it too."""
    compute_dt = dtype_of(cfg.compute_dtype)
    x = sc.act(jnp.take(params["embed"], tokens, axis=0).astype(compute_dt),
               "dp", None, None)
    pos = cache["pos"]
    x, new_caches = decode_blocks(cfg, params["layers"], cache["layers"], x,
                                  pos, impl=impl)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ _head(cfg, params).astype(x.dtype)
    return logits, {"pos": pos + 1, "layers": new_caches}


# ===========================================================================
def build_model(cfg: ModelConfig, impl: str | None = None) -> Model:
    return Model(
        cfg=cfg,
        init=functools.partial(init_params, cfg),
        loss_fn=functools.partial(loss_fn, cfg, impl=impl),
        forward=functools.partial(logits_fn, cfg, impl=impl),
        prefill=functools.partial(prefill, cfg, impl=impl),
        decode_step=functools.partial(decode_step, cfg, impl=impl),
        init_cache=functools.partial(init_cache, cfg),
    )
