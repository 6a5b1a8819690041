"""Arithmetic shared by the plain references: float32 matrix products at
full precision, the same products in a lower precision for the control,
RMSNorm, and the read-out that turns hidden states into logit gaps.

Nothing here imports the program under test."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _scaled(x, axis, top):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    return jnp.where(s > 0, s, 1.0)


def quantize(x, axis, quant):
    """``x`` rounded to ``quant`` ("int8" or "fp8") with one scale per
    slice along ``axis`` (symmetric, abs-max), returned in float32."""
    x = x.astype(F32)
    if quant == "int8":
        s = _scaled(x, axis, 127.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = _scaled(x, axis, FP8_MAX)
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown precision {quant!r}")


def mm(x, w, quant=None):
    """``x @ w`` in float32 at full precision; with ``quant``, both
    operands are first rounded to that precision (activations per row,
    weights per output column), as a W8A8 product would."""
    x = x.astype(F32)
    w = w.astype(F32)
    if quant is not None:
        x = quantize(x, -1, quant)
        w = quantize(w, -2, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def embed_in(params, tokens, m: dict):
    """Token ids (B, T) -> float32 rows (B, T, d) of ``params["embed"]``."""
    return jnp.take(params["embed"], tokens, axis=0).astype(F32)


def tied_readout(params, x, positions, served, probes, m: dict, quant=None):
    """`readout` of the hidden states ``x`` (B, T, d) at ``positions``
    (B, K), final-normed, against the tied head ``params["embed"]``."""
    h = jnp.take_along_axis(x, positions[..., None], axis=1)    # (B, K, d)
    h = rmsnorm(h, params["final_norm"], m["norm_eps"])
    return readout(h, params["embed"], served, probes, quant)


def readout(h, head_rows, served, probes, quant=None):
    """Logits of the normed hidden states ``h`` (B, K, d) against the
    tied head ``head_rows`` (V, d), then per position: how far the
    served token's logit and the probe token's logit lie below the best
    one, and which token is best.  ``served``/``probes`` (B, K) int32;
    -1 marks a position that is not read (gaps 0 there)."""
    logits = mm(h, head_rows.T, quant)                       # (B, K, V)
    best = jnp.max(logits, axis=-1)

    def gap(tok):
        at = jnp.take_along_axis(logits, jnp.maximum(tok, 0)[..., None], axis=-1)[..., 0]
        return jnp.where(tok >= 0, best - at, 0.0)
    return gap(served), gap(probes), jnp.argmax(logits, axis=-1).astype(jnp.int32)


def causal_conv(x, w):
    """Depthwise causal convolution over time: x (B, T, C), w (K, C); tap
    k multiplies the input K-1-k steps back."""
    k_taps, t = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for k in range(k_taps):
        shift = k_taps - 1 - k
        xs = x if shift == 0 else jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :t]
        out = out + xs * w[k].astype(F32)
    return out
