"""Error-feedback int8 gradient compression with ring reduce-scatter.

Why a custom ring: the obvious "quantize + all-gather" moves (n-1)*N int8
bytes per device — MORE than a ring all-reduce's 2(n-1)/n*N*4 f32 bytes
once n > 8.  The right primitive is a *quantized ring reduce-scatter*
(reduce chunks hop-by-hop, requantizing per hop) followed by an int8 ring
all-gather: per-device wire = 2(n-1)/n * N int8 bytes — 4x less than an
f32 ring all-reduce at any n.  Both rings are jax-native (`shard_map` +
`lax.ppermute`), so they lower to collective-permute chains that the
dry-run's HLO parser prices like any other collective
(benchmarks/bench_compress.py shows the measured wire ratio).

Per-hop requantization is lossy; the **error-feedback** buffer carries the
residual into the next step (EF-SGD-style), which preserves convergence —
tests/test_compress.py checks the EF contract (residual = exactly what was
not communicated) and end-to-end training parity on the bigram task.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# --------------------------------------------------------------- int8 -----
def quantize_int8(x):
    """Symmetric global-scale int8: returns (q, scale) with scale ()."""
    a = jnp.max(jnp.abs(x))
    scale = (jnp.maximum(a, 1e-12) / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def ef_compress(x, err):
    """Error-feedback quantization: returns ((q, scale), new_err) with the
    contract  dequant(q, scale) + new_err == x + err  (exactly)."""
    corrected = x.astype(jnp.float32) + err
    q, s = quantize_int8(corrected)
    return (q, s), corrected - dequantize_int8(q, s)


# ------------------------------------------------- ring reduce-scatter -----
def ring_reduce_scatter_int8(x, axis_name: str, n: int):
    """Quantized ring RS over a named axis.  x: flat f32, size % n == 0.
    Returns this device's reduced chunk (f32, size |x|/n).
    Per-device wire: (n-1)/n * |x| int8 bytes (+ n-1 scalar scales)."""
    idx = jax.lax.axis_index(axis_name)
    chunks = x.reshape(n, -1)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Device d injects chunk (d-1)%n; after hop i (1-based) it holds the
    # partial for chunk (d-1-i)%n and adds its own contribution; after
    # n-1 hops it holds the full sum of chunk d.
    def body(i, carry):
        q, s = carry
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        take = (idx - i - 2) % n
        summed = dequantize_int8(q, s) + chunks[take]
        return quantize_int8(summed)

    q0, s0 = quantize_int8(chunks[(idx - 1) % n])
    qf, sf = jax.lax.fori_loop(0, n - 1, body, (q0, s0))
    return dequantize_int8(qf, sf)


def ring_all_gather_int8(chunk, axis_name: str, n: int):
    """int8 ring AG of per-device chunks -> full flat f32 buffer.
    Per-device wire: (n-1)/n * |full| int8 bytes."""
    q, s = quantize_int8(chunk)
    perm = [(i, (i + 1) % n) for i in range(n)]
    idx = jax.lax.axis_index(axis_name)

    def body(i, carry):
        out_q, out_s, cur_q, cur_s = carry
        cur_q = jax.lax.ppermute(cur_q, axis_name, perm)
        cur_s = jax.lax.ppermute(cur_s, axis_name, perm)
        src = (idx - i - 1) % n
        out_q = jax.lax.dynamic_update_index_in_dim(out_q, cur_q, src, 0)
        out_s = jax.lax.dynamic_update_index_in_dim(out_s, cur_s, src, 0)
        return out_q, out_s, cur_q, cur_s

    out_q = jnp.zeros((n, *q.shape), jnp.int8)
    out_s = jnp.zeros((n,), jnp.float32)
    out_q = jax.lax.dynamic_update_index_in_dim(out_q, q, idx, 0)
    out_s = jax.lax.dynamic_update_index_in_dim(out_s, s, idx, 0)
    out_q, out_s, _, _ = jax.lax.fori_loop(0, n - 1, body,
                                           (out_q, out_s, q, s))
    return (out_q.astype(jnp.float32) * out_s[:, None]).reshape(-1)


def compressed_mean(x, axis_name: str, n: int):
    """Drop-in mean-over-axis: int8 ring RS + int8 ring AG (+EF outside)."""
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunk = ring_reduce_scatter_int8(flat, axis_name, n)
    full = ring_all_gather_int8(chunk, axis_name, n)
    if pad:
        full = full[:-pad]
    return (full / n).reshape(x.shape)


# ----------------------------------------------------------- high level ----
@dataclass(frozen=True)
class CompressionState:
    """Per-device error-feedback buffers, stacked on a leading device dim
    (n, *leaf.shape), sharded over the sync axis."""
    err: dict

    @classmethod
    def init(cls, params, n: int):
        return cls(err=jax.tree.map(
            lambda p: jnp.zeros((n, *p.shape), jnp.float32), params))


def make_compressed_sync(mesh, axis: str = "data"):
    """Returns sync(local_grads, state) -> (synced, state').

    ``local_grads``: pytree with leading device dim (n, ...) sharded over
    ``axis`` — row i is device i's unreduced gradient.  ``synced`` has the
    same stacked layout; every row equals the EF-corrected int8-ring mean.
    """
    n = mesh.shape[axis]

    def body(g_tree, err_tree):
        def one(g, e):
            g = g[0].astype(jnp.float32)
            e = e[0]
            gc = g + e
            synced = compressed_mean(gc, axis, n)
            return synced[None], (gc - synced)[None]
        pairs = jax.tree.map(one, g_tree, err_tree)
        synced = jax.tree.map(lambda p: p[0], pairs,
                              is_leaf=lambda x: isinstance(x, tuple))
        errs = jax.tree.map(lambda p: p[1], pairs,
                            is_leaf=lambda x: isinstance(x, tuple))
        return synced, errs

    def sync(local_grads, state: CompressionState):
        spec = jax.tree.map(lambda _: P(axis), local_grads)
        f = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                      out_specs=(spec, spec), check_vma=False)
        synced, new_err = f(local_grads, state.err)
        return synced, CompressionState(err=new_err)

    return sync
