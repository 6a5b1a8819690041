"""Plain float32 reference of a decoder-only transformer with grouped-query
attention, rotary positions, RMSNorm, a SiLU-gated MLP and a tied head,
as Qwen2 publishes it (Qwen/Qwen2.5-3B config.json and the Qwen2 model
description): every sublayer pre-norm and residual, biases on the q/k/v
projections, rotary embedding over halves of each head.

No cache and no kernels: the whole sequence in one pass, layer by layer,
in the three pieces `chipbench.refpass` composes (`embed_in`,
`layer_stack`, `readout`).  The parameter tree is the one the benchmark
draws (`layout`); its names follow the serving program's so the same
arrays can be handed to it.  The kind's counts for the yardstick
(`layer_flops`, `layer_step_bytes`) sit here too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import refmath
from chipbench.counts import ACT_BYTES, PARAM_BYTES
from chipbench.refmath import F32, HIGHEST, mm, rmsnorm


def layout(m: dict) -> dict:
    L, d, ff = m["n_layers"], m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    wt = m["param_dtype"]
    mixer = {
        "norm": ((L, d), "float32", ("ones_noise", 0.1)),
        "wq": ((L, d, q), wt, ("fan_in",)),
        "wk": ((L, d, kv), wt, ("fan_in",)),
        "wv": ((L, d, kv), wt, ("fan_in",)),
        "wo": ((L, q, d), wt, ("fan_in",)),
    }
    if m["qkv_bias"]:
        mixer.update({"bq": ((L, q), wt, ("normal", 0.1)),
                      "bk": ((L, kv), wt, ("normal", 0.1)),
                      "bv": ((L, kv), wt, ("normal", 0.1))})
    mlp = {"norm": ((L, d), "float32", ("ones_noise", 0.1)),
           "w_gate": ((L, d, ff), wt, ("fan_in",)),
           "w_up": ((L, d, ff), wt, ("fan_in",)),
           "w_down": ((L, ff, d), wt, ("fan_in",))}
    out = {("embed",): ((m["vocab_rows"], d), wt, ("normal", 0.02)),
           ("final_norm",): ((d,), "float32", ("ones_noise", 0.1))}
    out.update({("layers", "pos0", "mixer", k): v for k, v in mixer.items()})
    out.update({("layers", "pos0", "mlp", k): v for k, v in mlp.items()})
    return out


def layer_flops(m: dict, ctx: int) -> float:
    """Forward FLOPs of one layer for one token attending ``ctx``
    positions: the q/k/v/o projections, the gated MLP, q.k and p.v."""
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    proj = 2 * d * (H + 2 * KV) * hd + 2 * H * hd * d
    mlp = 2 * 3 * d * m["d_ff"]
    return proj + mlp + 4 * H * hd * ctx


def layer_step_bytes(m: dict, batch: int, cache_len: int) -> float:
    """Bytes one layer's decode step must move, its activation in and out
    aside: norm gains and weights read once, the live k and v read, one
    slot of each written."""
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pb = PARAM_BYTES[m["param_dtype"]]
    per = d * 4 + (d * (H + 2 * KV) * hd + H * hd * d) * pb
    if m["qkv_bias"]:
        per += (H + 2 * KV) * hd * pb
    per += batch * cache_len * KV * hd * ACT_BYTES * 2      # live k, v read
    per += batch * KV * hd * ACT_BYTES * 2                  # one slot written
    return per + d * 4 + 3 * d * m["d_ff"] * pb             # mlp norm, weights


def _rope(x, theta):
    """x (B, T, heads, hd): rotate the first half of each head against
    the second by angle position * theta ** (-i / (hd/2))."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq               # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _layer(m, quant, x, p):
    B, T, _ = x.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a, f = p["mixer"], p["mlp"]
    h = rmsnorm(x, a["norm"], m["norm_eps"])
    q, k, v = mm(h, a["wq"], quant), mm(h, a["wk"], quant), mm(h, a["wv"], quant)
    if m["qkv_bias"]:
        q, k, v = q + a["bq"].astype(F32), k + a["bk"].astype(F32), v + a["bv"].astype(F32)
    q = _rope(q.reshape(B, T, H, hd), m["rope_theta"])
    k = _rope(k.reshape(B, T, KV, hd), m["rope_theta"])
    v = v.reshape(B, T, KV, hd)
    q = q.reshape(B, T, KV, H // KV, hd)
    s = jnp.einsum("bqgrh,bkgh->bgrqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bgrqk,bkgh->bqgrh", jax.nn.softmax(s, axis=-1), v, precision=HIGHEST)
    x = x + mm(o.reshape(B, T, H * hd), a["wo"], quant)
    h = rmsnorm(x, f["norm"], m["norm_eps"])
    g = jax.nn.silu(mm(h, f["w_gate"], quant)) * mm(h, f["w_up"], quant)
    return x + mm(g, f["w_down"], quant)


# the embedding and the read-out are the tied head's, shared by the kinds
embed_in = refmath.embed_in
readout = refmath.tied_readout


def layer_stack(layers, x, m: dict, quant=None):
    """Every period of the stacked ``layers`` over ``x`` (B, T, d), in order."""
    def body(h, p):
        return _layer(m, quant, h, p["pos0"]), None
    x, _ = jax.lax.scan(body, x, layers)
    return x
