"""Plain float32 reference of an attention-free Mamba2 language model
(arXiv:2405.21060): pre-norm residual blocks, each an SSD mixer with one
group of B/C shared by all heads, a depthwise causal convolution, the
gated RMSNorm (norm of y * silu(z)) and a tied head.

The state recurrence is run token by token, the plain form of SSD:
    s_t = exp(dt_t * A) s_{t-1} + dt_t * x_t B_t^T,   y_t = s_t C_t + D x_t
with A = -exp(a_log) and dt_t = softplus(dt_raw_t + dt_bias).

Where the served configuration departs from the published block it is
followed here and named in the configuration's file: the convolution
runs over x alone and has no bias (the published block convolves x, B
and C together, with a bias).  The kind's counts for the yardstick
(`layer_flops`, `layer_step_bytes`) sit here too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import refmath
from chipbench.counts import ACT_BYTES, PARAM_BYTES
from chipbench.refmath import F32, mm, rmsnorm


def _sizes(m):
    di = m["expand"] * m["d_model"]
    return di, di // m["head_dim"], m["d_state"]


def layer_flops(m: dict, ctx: int) -> float:
    """Forward FLOPs of one layer for one token, whatever its context:
    the in-projections (x and z; B, C and dt), the convolution, the state
    update and read-out, the out-projection."""
    d = m["d_model"]
    di, H, N = _sizes(m)
    proj = 2 * d * 2 * di + 2 * d * (2 * m["n_groups"] * N + H) + 2 * di * d
    return proj + 2 * m["d_conv"] * di + 4 * H * m["head_dim"] * N


def layer_step_bytes(m: dict, batch: int, cache_len: int) -> float:
    """Bytes one layer's decode step must move, its activation in and out
    aside: weights and float32 parameters read once, the convolution's
    history and the float32 state read and written."""
    d = m["d_model"]
    di, H, N = _sizes(m)
    pb = PARAM_BYTES[m["param_dtype"]]
    per = d * 4 + (d * 2 * di + d * (2 * m["n_groups"] * N + H)
                   + m["d_conv"] * di + di * d) * pb
    per += (3 * H + di) * 4                                 # dt_bias, a_log, D, gate norm
    per += 2 * batch * (m["d_conv"] - 1) * di * ACT_BYTES   # conv history r+w
    per += 2 * batch * H * m["head_dim"] * N * 4            # f32 state r+w
    return per + d * 4                                      # the unused mlp norm


def layout(m: dict) -> dict:
    L, d, wt = m["n_layers"], m["d_model"], m["param_dtype"]
    di, H, N = _sizes(m)
    mixer = {
        "norm": ((L, d), "float32", ("ones_noise", 0.1)),
        "w_xz": ((L, d, 2 * di), wt, ("fan_in",)),
        "w_bcdt": ((L, d, 2 * m["n_groups"] * N + H), wt, ("fan_in",)),
        "conv_w": ((L, m["d_conv"], di), wt, ("uniform", -0.5, 0.5)),
        "dt_bias": ((L, H), "float32", ("dt_bias", 1e-3, 1e-1)),
        "a_log": ((L, H), "float32", ("log_uniform", 1.0, 16.0)),
        "d_skip": ((L, H), "float32", ("ones_noise", 0.1)),
        "gate_norm": ((L, di), "float32", ("ones_noise", 0.1)),
        "w_out": ((L, di, d), wt, ("fan_in",)),
    }
    out = {("embed",): ((m["vocab_rows"], d), wt, ("normal", 0.02)),
           ("final_norm",): ((d,), "float32", ("ones_noise", 0.1)),
           # the block has no MLP; the serving program still holds its
           # norm gain, which nothing reads
           ("layers", "pos0", "mlp", "norm"): ((L, d), "float32", ("ones_noise", 0.1))}
    out.update({("layers", "pos0", "mixer", k): v for k, v in mixer.items()})
    return out


def _ssd(x, dt, a, b, c):
    """x (B, T, H, P), dt (B, T, H), a (H,), b/c (B, T, N) -> y (B, T, H, P)."""
    Bn, _, H, P = x.shape
    N = b.shape[-1]

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = s * jnp.exp(dtt * a)[:, :, None, None] \
            + dtt[:, :, None, None] * xt[..., None] * bt[:, None, None, :]
        return s, jnp.einsum("bhpn,bn->bhp", s, ct, precision=refmath.HIGHEST)

    s0 = jnp.zeros((Bn, H, P, N), F32)
    seq = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    _, y = jax.lax.scan(step, s0, seq)
    return jnp.moveaxis(y, 0, 1)


def _layer(m, quant, x, p):
    B, T, _ = x.shape
    di, H, N = _sizes(m)
    a = p["mixer"]
    h = rmsnorm(x, a["norm"], m["norm_eps"])
    xz = mm(h, a["w_xz"], quant)
    x_in, z = xz[..., :di], xz[..., di:]
    bcdt = mm(h, a["w_bcdt"], quant)
    b, c, dt_raw = bcdt[..., :N], bcdt[..., N:2 * N], bcdt[..., 2 * N:]
    dt = jax.nn.softplus(dt_raw + a["dt_bias"])
    xh = jax.nn.silu(refmath.causal_conv(x_in, a["conv_w"])).reshape(B, T, H, m["head_dim"])
    y = _ssd(xh, dt, -jnp.exp(a["a_log"]), b, c) + xh * a["d_skip"][:, None]
    y = y.reshape(B, T, di) * jax.nn.silu(z)
    y = rmsnorm(y, a["gate_norm"], m["norm_eps"])
    return x + mm(y, a["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _gaps(params, tokens, positions, served, probes, *, m, quant):
    m = dict(m)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)

    def body(h, p):
        return _layer(m, quant, h, p["pos0"]), None
    x, _ = jax.lax.scan(body, x, params["layers"])
    h = jnp.take_along_axis(x, positions[..., None], axis=1)
    h = rmsnorm(h, params["final_norm"], m["norm_eps"])
    return refmath.readout(h, params["embed"], served, probes, quant)


def gaps(params, m: dict, tokens, positions, served, probes, quant=None):
    """As `dense_gqa.gaps`, for this model."""
    return _gaps(params, tokens, positions, served, probes,
                 m=tuple(sorted(m.items())), quant=quant)
