"""Transformer / Mamba2 / MoE blocks: init + forward + single-token decode.

All block params are plain dict pytrees; callers stack them over layer
periods and scan.  Forward functions take and return (B, S, D) activations
in the compute dtype; decode functions operate on one token with explicit
cache state (functional, no mutation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import AttnCfg, MambaCfg, ModelConfig, MoECfg
from ..kernels import ops, ref
from .common import KeyGen, activation, dense_init, rmsnorm, rope
from .. import sharding_ctx as sc


# ===========================================================================
# Attention
# ===========================================================================
def init_attn(kg: KeyGen, cfg: ModelConfig, tag: str, cross: bool = False):
    a = cfg.attn
    d, hd = cfg.d_model, a.head_dim
    dt = jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16
    p = {
        "norm": jnp.ones((d,), jnp.float32),
        "wq": dense_init(kg(tag, "wq"), (d, a.n_heads * hd), dt),
        "wk": dense_init(kg(tag, "wk"), (d, a.n_kv_heads * hd), dt),
        "wv": dense_init(kg(tag, "wv"), (d, a.n_kv_heads * hd), dt),
        "wo": dense_init(kg(tag, "wo"), (a.n_heads * hd, d), dt),
    }
    if a.qkv_bias:
        p["bq"] = jnp.zeros((a.n_heads * hd,), dt)
        p["bk"] = jnp.zeros((a.n_kv_heads * hd,), dt)
        p["bv"] = jnp.zeros((a.n_kv_heads * hd,), dt)
    return p


def _qkv(p, cfg: ModelConfig, x, positions, *, rope_q=True):
    a = cfg.attn
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if a.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = sc.act(q.reshape(B, S, a.n_heads, a.head_dim), "dp", None, "tp", None)
    k = sc.act(k.reshape(B, S, a.n_kv_heads, a.head_dim), "dp", None, "tp", None)
    v = sc.act(v.reshape(B, S, a.n_kv_heads, a.head_dim), "dp", None, "tp", None)
    if rope_q:
        q = rope(q, positions, a.rope_theta)
        k = rope(k, positions, a.rope_theta)
    return q, k, v


def attn_forward(p, cfg: ModelConfig, x, positions, *, causal=True,
                 impl=None, return_kv=False):
    """Self-attention sublayer (pre-norm, residual)."""
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, positions)
    o = ops.attention(q, k, v, causal=causal,
                      window=cfg.attn.window if causal else None, impl=impl)
    B, S, _ = x.shape
    out = sc.act(x + o.reshape(B, S, -1) @ p["wo"].astype(x.dtype),
                 "dp", "sp", None)
    if return_kv:
        return out, (k, v)
    return out


def cross_attn_forward(p, cfg: ModelConfig, x, enc_kv, *, impl=None):
    """Cross-attention sublayer; enc_kv = (k, v) precomputed from encoder."""
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    a = cfg.attn
    B, S, _ = x.shape
    q = (h @ p["wq"].astype(x.dtype))
    if a.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, S, a.n_heads, a.head_dim)
    k, v = enc_kv
    o = ops.attention(q, k, v, causal=False, impl=impl)
    return sc.act(x + o.reshape(B, S, -1) @ p["wo"].astype(x.dtype),
                  "dp", "sp", None)


def cross_kv(p, cfg: ModelConfig, enc_out):
    """Precompute cross-attention K/V from encoder output (B, Se, D)."""
    a = cfg.attn
    B, Se, _ = enc_out.shape
    k = enc_out @ p["wk"].astype(enc_out.dtype)
    v = enc_out @ p["wv"].astype(enc_out.dtype)
    if a.qkv_bias:
        k = k + p["bk"].astype(enc_out.dtype)
        v = v + p["bv"].astype(enc_out.dtype)
    return (k.reshape(B, Se, a.n_kv_heads, a.head_dim),
            v.reshape(B, Se, a.n_kv_heads, a.head_dim))


def attn_cache_capacity(cfg: ModelConfig, seq_len: int) -> int:
    w = cfg.attn.window if cfg.attn else None
    return min(seq_len, w) if w else seq_len


def init_attn_cache(cfg: ModelConfig, batch: int, capacity: int, dtype):
    a = cfg.attn
    shape = (batch, capacity, a.n_kv_heads, a.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_decode(p, cfg: ModelConfig, x, cache, pos, *, impl=None):
    """One-token self-attention.  x: (B, 1, D); cache {k,v}: (B, C, KV, hd);
    pos: () int32 absolute position.  Ring-buffered for SWA.

    Every impl except ``"ref"`` routes through the fused step
    (`kernels.ops.attn_decode_step`: rmsnorm + QKV + rope + cache write +
    decode attention + output proj in one call); ``"ref"`` keeps the
    historical op-by-op body verbatim — the bitwise oracle the serving
    parity tests pin.  Both return caches with the input avals
    leaf-for-leaf (the `lm.decode_cache_structs` donation contract)."""
    a = cfg.attn
    B = x.shape[0]
    mode = ops.resolve_impl(impl)
    if mode != "ref":
        o, k_cache, v_cache = ops.attn_decode_step(
            x, cache["k"], cache["v"], pos,
            norm=p["norm"], wq=p["wq"], wk=p["wk"], wv=p["wv"], wo=p["wo"],
            bq=p.get("bq"), bk=p.get("bk"), bv=p.get("bv"),
            n_heads=a.n_heads, head_dim=a.head_dim, eps=cfg.norm_eps,
            rope_theta=a.rope_theta, impl=mode)
        return sc.act(o, "dp", "sp", None), {"k": k_cache, "v": v_cache}
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, jnp.full((1,), pos))
    C = cache["k"].shape[1]
    slot = jnp.mod(pos, C)
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                           (0, slot, 0, 0))
    cache_len = jnp.minimum(pos + 1, C)
    o = ref.decode_attention_ref(q[:, 0], k_cache, v_cache, cache_len)
    out = sc.act(x + o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype),
                 "dp", "sp", None)
    return out, {"k": k_cache, "v": v_cache}


def cross_attn_decode(p, cfg: ModelConfig, x, enc_kv, *, impl=None):
    a = cfg.attn
    B = x.shape[0]
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q = h @ p["wq"].astype(x.dtype)
    if a.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, a.n_heads, a.head_dim)
    k, v = enc_kv
    # impl-dispatched like every other attention site (`set_default_impl`
    # / REPRO_KERNEL_IMPL govern this one too); "ref" is the old call
    o = ops.decode_attention(q, k, v, k.shape[1], impl=impl)
    return x + o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)


# ===========================================================================
# Mamba2 (SSD)
# ===========================================================================
def init_mamba(kg: KeyGen, cfg: ModelConfig, tag: str):
    m = cfg.mamba
    d = cfg.d_model
    di = m.d_inner(d)
    H = m.n_ssm_heads(d)
    N = m.d_state
    dt = jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16
    return {
        "norm": jnp.ones((d,), jnp.float32),
        "w_xz": dense_init(kg(tag, "w_xz"), (d, 2 * di), dt),
        "w_bcdt": dense_init(kg(tag, "w_bcdt"), (d, 2 * m.n_groups * N + H), dt),
        "conv_w": dense_init(kg(tag, "conv"), (m.d_conv, di), dt, scale=0.5),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "a_log": jnp.zeros((H,), jnp.float32),           # A = -exp(a_log) = -1
        "d_skip": jnp.ones((H,), jnp.float32),
        "gate_norm": jnp.ones((di,), jnp.float32),
        "w_out": dense_init(kg(tag, "w_out"), (di, d), dt),
    }


def _mamba_proj(p, cfg: ModelConfig, h):
    m = cfg.mamba
    d = cfg.d_model
    di = m.d_inner(d)
    H = m.n_ssm_heads(d)
    N = m.d_state
    xz = h @ p["w_xz"].astype(h.dtype)
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in = sc.act(x_in, "dp", None, "tp")
    z = sc.act(z, "dp", None, "tp")
    bcdt = sc.act(h @ p["w_bcdt"].astype(h.dtype), "dp", "sp", None)
    b = bcdt[..., :N]
    c = bcdt[..., N:2 * N]
    dt_raw = bcdt[..., 2 * N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    return x_in, z, b, c, dt


def mamba_forward(p, cfg: ModelConfig, x, *, impl=None, chunk=128):
    """Mamba2 block (pre-norm, residual).  x: (B, S, D)."""
    m = cfg.mamba
    B, S, _ = x.shape
    di = m.d_inner(cfg.d_model)
    H = m.n_ssm_heads(cfg.d_model)
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    x_in, z, b, c, dt = _mamba_proj(p, cfg, h)
    # depthwise causal conv (d_conv taps) as shifted adds
    w = p["conv_w"].astype(x_in.dtype)
    conv = jnp.zeros_like(x_in)
    for k in range(m.d_conv):
        shift = m.d_conv - 1 - k
        sl = x_in if shift == 0 else jnp.pad(x_in, ((0, 0), (shift, 0), (0, 0)))[:, :S]
        conv = conv + sl * w[k]
    xh = sc.act(jax.nn.silu(conv).reshape(B, S, H, m.head_dim),
                "dp", None, "tp", None)
    a = -jnp.exp(p["a_log"])
    y, _ = ops.ssd(xh, dt, a, b, c, chunk=chunk, impl=impl)
    y = y + xh * p["d_skip"][None, None, :, None].astype(xh.dtype)
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    y = rmsnorm(y, p["gate_norm"], cfg.norm_eps)
    return sc.act(x + y @ p["w_out"].astype(x.dtype), "dp", "sp", None)


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype):
    m = cfg.mamba
    di = m.d_inner(cfg.d_model)
    H = m.n_ssm_heads(cfg.d_model)
    return {
        "conv": jnp.zeros((batch, m.d_conv - 1, di), dtype),
        "ssm": jnp.zeros((batch, H, m.head_dim, m.d_state), jnp.float32),
    }


def mamba_decode(p, cfg: ModelConfig, x, cache, *, impl=None):
    """One-token Mamba2 step.  x: (B, 1, D)."""
    m = cfg.mamba
    B = x.shape[0]
    di = m.d_inner(cfg.d_model)
    H = m.n_ssm_heads(cfg.d_model)
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    x_in, z, b, c, dt = _mamba_proj(p, cfg, h)
    x_in, z, b, c, dt = x_in[:, 0], z[:, 0], b[:, 0], c[:, 0], dt[:, 0]
    w = p["conv_w"].astype(x_in.dtype)
    hist = cache["conv"]                                  # (B, d_conv-1, di)
    conv = x_in * w[-1] + jnp.einsum("bkd,kd->bd", hist.astype(x_in.dtype), w[:-1])
    conv_new = jnp.concatenate([hist[:, 1:], x_in[:, None].astype(hist.dtype)], axis=1)
    xh = jax.nn.silu(conv).reshape(B, H, m.head_dim)
    a = -jnp.exp(p["a_log"])
    y, ssm_new = ref.ssd_decode_step(cache["ssm"], xh, dt, a, b, c)
    y = y + xh * p["d_skip"][None, :, None].astype(xh.dtype)
    y = y.reshape(B, di) * jax.nn.silu(z)
    y = rmsnorm(y, p["gate_norm"], cfg.norm_eps)
    out = sc.act(x + (y @ p["w_out"].astype(x.dtype))[:, None],
                 "dp", "sp", None)
    return out, {"conv": conv_new, "ssm": sc.act(ssm_new, "dp", "tp", None, None)}


# ===========================================================================
# MLP / MoE
# ===========================================================================
def _init_ffn(kg: KeyGen, cfg: ModelConfig, tag: str, d_ff: int, dt,
              expert_dims: tuple[int, ...] = ()):
    d = cfg.d_model
    gated = cfg.act == "silu_glu"
    p = {}
    if gated:
        p["w_gate"] = dense_init(kg(tag, "w_gate"), (*expert_dims, d, d_ff), dt)
    p["w_up"] = dense_init(kg(tag, "w_up"), (*expert_dims, d, d_ff), dt)
    p["w_down"] = dense_init(kg(tag, "w_down"), (*expert_dims, d_ff, d), dt)
    return p


def _ffn(p, cfg: ModelConfig, h):
    if cfg.act == "silu_glu":
        act = sc.act(jax.nn.silu(h @ p["w_gate"].astype(h.dtype)),
                     "dp", None, "tp")
        up = sc.act(h @ p["w_up"].astype(h.dtype), "dp", None, "tp")
        return (act * up) @ p["w_down"].astype(h.dtype)
    act = sc.act(activation(cfg.act)(h @ p["w_up"].astype(h.dtype)),
                 "dp", None, "tp")
    return act @ p["w_down"].astype(h.dtype)


def init_mlp(kg: KeyGen, cfg: ModelConfig, tag: str):
    dt = jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16
    if cfg.d_ff == 0:  # attn-free Mamba2 stacks carry no MLP sublayer
        return {"norm": jnp.ones((cfg.d_model,), jnp.float32)}
    p = {"norm": jnp.ones((cfg.d_model,), jnp.float32)}
    p.update(_init_ffn(kg, cfg, tag, cfg.d_ff, dt))
    return p


def mlp_forward(p, cfg: ModelConfig, x):
    if cfg.d_ff == 0:
        return x
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    return sc.act(x + _ffn(p, cfg, h), "dp", "sp", None)


def init_moe(kg: KeyGen, cfg: ModelConfig, tag: str):
    e = cfg.moe
    dt = jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16
    p = {"norm": jnp.ones((cfg.d_model,), jnp.float32),
         "router": dense_init(kg(tag, "router"), (cfg.d_model, e.n_experts),
                              jnp.float32, scale=0.02)}
    p["experts"] = _init_ffn(kg, cfg, tag + ".experts", e.d_ff, dt,
                             expert_dims=(e.n_experts,))
    if e.shared_expert:
        p["shared"] = _init_ffn(kg, cfg, tag + ".shared", e.d_ff, dt)
    return p


def _expert_ffn(p, cfg: ModelConfig, xe):
    """xe: (B, E, C, D) -> (B, E, C, D) via per-expert FFN weights."""
    if cfg.act == "silu_glu":
        act = jax.nn.silu(jnp.einsum("becd,edf->becf", xe,
                                     p["w_gate"].astype(xe.dtype)))
        up = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(xe.dtype))
        return jnp.einsum("becf,efd->becd", act * up, p["w_down"].astype(xe.dtype))
    act = activation(cfg.act)(jnp.einsum("becd,edf->becf", xe,
                                         p["w_up"].astype(xe.dtype)))
    return jnp.einsum("becf,efd->becd", act, p["w_down"].astype(xe.dtype))


MOE_IMPL = "einsum"     # "einsum" (GShard dense) | "sorted" (ragged a2a)


def set_moe_impl(name: str) -> None:
    global MOE_IMPL
    assert name in ("einsum", "sorted")
    MOE_IMPL = name


def moe_forward(p, cfg: ModelConfig, x):
    """GShard-style top-k dispatch with capacity (einsum dispatch/combine).

    Token dim shards over data axes; expert dim shards over the model axis
    (expert parallelism).  x: (B, S, D).  ``set_moe_impl("sorted")``
    switches to the ragged sorted-dispatch path (moe_forward_sorted)."""
    if MOE_IMPL == "sorted":
        return moe_forward_sorted(p, cfg, x)
    e = cfg.moe
    B, S, D = x.shape
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    logits = (h.astype(jnp.float32) @ p["router"])           # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    cap = max(1, int(S * e.capacity_factor * e.top_k / e.n_experts))

    out = jnp.zeros_like(h)
    remaining = probs
    occupancy = jnp.zeros((B, e.n_experts), jnp.int32)
    for _ in range(e.top_k):
        idx = jnp.argmax(remaining, axis=-1)                  # (B, S)
        gate = jnp.take_along_axis(remaining, idx[..., None], axis=-1)[..., 0]
        onehot = jax.nn.one_hot(idx, e.n_experts, dtype=jnp.int32)  # (B,S,E)
        pos = jnp.cumsum(onehot, axis=1) - onehot + occupancy[:, None, :]
        pos_tok = jnp.sum(pos * onehot, axis=-1)              # (B, S)
        keep = pos_tok < cap
        disp = (jax.nn.one_hot(idx, e.n_experts, dtype=h.dtype)[..., :, None]
                * jax.nn.one_hot(pos_tok, cap, dtype=h.dtype)[..., None, :]
                * keep[..., None, None].astype(h.dtype))      # (B,S,E,C)
        # dispatched tensor: expert dim on the EP axis.  ep_data: tokens
        # all-to-all to the data row owning their expert (expert weights
        # are NEVER gathered); ep_model: experts on the model axis (naive).
        xe = sc.act(jnp.einsum("bsd,bsec->becd", h, disp),
                    "ep_tok", "ep", None, None)
        ye = _expert_ffn(p["experts"], cfg, xe)
        ye = sc.act(ye, "ep_tok", "ep", None, None)
        out = out + jnp.einsum("becd,bsec->bsd", ye,
                               disp * gate[..., None, None].astype(h.dtype))
        occupancy = occupancy + jnp.sum(onehot * keep[..., None].astype(jnp.int32), axis=1)
        remaining = remaining * (1.0 - jax.nn.one_hot(idx, e.n_experts))
    if e.shared_expert:
        out = out + _ffn(p["shared"], cfg, h)
    return sc.act(x + out.astype(x.dtype), "dp", "sp", None)




# ---------------------------------------------------------------------------
# Sorted (ragged) MoE dispatch — Switch/Tutel-style, beyond-paper (§Perf B)
# ---------------------------------------------------------------------------
def _ffn2(wg, wu, wd, cfg: ModelConfig, h):
    """Per-expert FFN on (E, C, D) buffers with local weight shards."""
    if cfg.act == "silu_glu":
        a = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg.astype(h.dtype)))
        u = jnp.einsum("ecd,edf->ecf", h, wu.astype(h.dtype))
        return jnp.einsum("ecf,efd->ecd", a * u, wd.astype(h.dtype))
    a = activation(cfg.act)(jnp.einsum("ecd,edf->ecf", h, wu.astype(h.dtype)))
    return jnp.einsum("ecf,efd->ecd", a, wd.astype(h.dtype))


def _sorted_dispatch_local(h2, probs, experts, cfg: ModelConfig, cap: int,
                           *, ep_axes=None, tp_axis=None, n_ep: int = 1):
    """Token-sorted top-k dispatch on one shard (or globally when no mesh).

    h2: (N, D) normed tokens; probs: (N, E) router probabilities.
    experts: dict of LOCAL expert weight shards (E or E/n_ep on dim 0).
    Inside shard_map: ep_axes carries the all-to-all (expert parallelism),
    tp_axis the within-expert psum (F sharded).  The (B,S,E,C) one-hot of
    the einsum path is never built: per round the traffic is one (E,C,D)
    buffer each way — measured 5.4 GB -> 52 MB per layer-pass on
    llama4-maverick (EXPERIMENTS.md §Perf Cell B).
    """
    e = cfg.moe
    N, D = h2.shape
    E = e.n_experts
    out = jnp.zeros((N, D), h2.dtype)
    remaining = probs
    for _ in range(e.top_k):
        ids = jnp.argmax(remaining, axis=-1)                    # (N,)
        gate = jnp.take_along_axis(remaining, ids[:, None], axis=-1)[:, 0]
        order = jnp.argsort(ids, stable=True)                   # tokens by expert
        ids_s = ids[order]
        counts = jnp.bincount(ids, length=E)
        starts = jnp.cumsum(counts) - counts                    # (E,)
        slot = jnp.arange(N) - starts[ids_s]                    # rank in expert
        slot = jnp.where(slot < cap, slot, cap)                 # cap -> dropped
        buf = jnp.zeros((E, cap, D), h2.dtype)
        buf = buf.at[ids_s, slot].set(h2[order], mode="drop")
        if ep_axes is not None:
            # exchange expert-major slices: (E, C, D) -> (E/n_ep, n_ep*C, D)
            buf = jax.lax.all_to_all(buf, ep_axes, split_axis=0,
                                     concat_axis=1, tiled=True)
        ye = _ffn2(experts["w_gate"], experts["w_up"], experts["w_down"],
                   cfg, buf) if "w_gate" in experts else             _ffn2(experts["w_up"], experts["w_up"], experts["w_down"],
                  cfg, buf)
        if tp_axis is not None:
            ye = jax.lax.psum(ye, tp_axis)                      # row-parallel F
        if ep_axes is not None:
            ye = jax.lax.all_to_all(ye, ep_axes, split_axis=1,
                                    concat_axis=0, tiled=True)
        tok = ye.at[ids_s, slot].get(mode="fill", fill_value=0)  # (N, D)
        contrib = jnp.zeros((N, D), h2.dtype).at[order].set(tok)
        out = out + contrib * gate[:, None].astype(h2.dtype)
        remaining = remaining * (1.0 - jax.nn.one_hot(ids, E,
                                                      dtype=remaining.dtype))
    return out


def moe_forward_sorted(p, cfg: ModelConfig, x):
    """Sorted-dispatch MoE block.  Under an active sharding context the
    dispatch runs in shard_map with explicit all_to_all/psum (experts on
    the data axes, F on the model axis — requires ep_axis="data" param
    layout); without a context it runs locally (CPU tests)."""
    e = cfg.moe
    B, S, D = x.shape
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    logits = (h.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    cap = max(1, int(S * e.capacity_factor * e.top_k / e.n_experts))
    ctx = sc.current()

    if ctx is None or ctx.mesh.shape[ctx.tp] * _prod_axes(ctx) == 1:
        out = _sorted_dispatch_local(
            h.reshape(B * S, D), probs.reshape(B * S, e.n_experts),
            p["experts"], cfg, cap)
        out = out.reshape(B, S, D)
    else:
        from jax.sharding import PartitionSpec as P
        mesh = ctx.mesh
        dp = ctx.dp
        ep_axes = ("data",)            # expert-parallel axis (a2a)
        n_ep = mesh.shape["data"]
        assert e.n_experts % n_ep == 0, (
            f"sorted MoE: {e.n_experts} experts must divide axis 'data' ({n_ep})")
        # per-shard capacity: local tokens only
        w_specs = {k: P(ep_axes, None, "model") if k in ("w_gate", "w_up")
                   else P(ep_axes, "model", None) for k in p["experts"]}

        def body(hl, pl, experts):
            N = hl.shape[0] * hl.shape[1]
            # per-shard capacity: proportional to LOCAL tokens
            capl = max(1, int(N * e.capacity_factor * e.top_k / e.n_experts))
            out = _sorted_dispatch_local(
                hl.reshape(N, D), pl.reshape(N, e.n_experts), experts, cfg,
                capl, ep_axes=ep_axes, tp_axis="model", n_ep=n_ep)
            return out.reshape(hl.shape)

        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(dp, None, None), P(dp, None, None), w_specs),
            out_specs=P(dp, None, None), check_vma=False,
        )(h, probs.astype(jnp.float32), p["experts"])
    if e.shared_expert:
        out = out + _ffn(p["shared"], cfg, h).astype(out.dtype)
    return sc.act(x + out.astype(x.dtype), "dp", "sp", None)


def _prod_axes(ctx) -> int:
    n = 1
    for a in ctx.dp:
        n *= ctx.mesh.shape[a]
    return n


def moe_decode(p, cfg: ModelConfig, x):
    """One-token MoE.  Tokens are routed independently (per-token capacity
    = top_k; no cross-batch competition) so the batch dim stays dp-sharded —
    flattening the batch into one token group would force a replicated
    dispatch (all tokens on every data row)."""
    return moe_forward(p, cfg, x)
