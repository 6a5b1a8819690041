"""Mamba2 SSD (state-space duality) chunked scan — Pallas TPU kernel.

Algorithm (arXiv 2405.21060, §6): split the sequence into chunks of Q
tokens.  Within a chunk the output is a masked, decay-weighted quadratic
form (MXU-friendly (Q x Q) @ (Q x P) matmuls); across chunks a (P x N)
state is carried.

TPU adaptation: the chunk axis is the innermost sequential grid dimension
and the running state lives in a VMEM scratch buffer — the systolic-array
analogue of the paper's inter-chunk recurrence (on GPU this is a separate
kernel launch + rescan).  Block shapes keep the (Q, N) and (Q, P) tiles
resident in VMEM; N = 128 matches the MXU lane width.

Layouts: x (B, H, L, P); dt (B, H, L); b, c (B, L, N); y (B, H, L, P);
final state (B, H, P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_out_ref,
                s_ref, *, chunk: int, num_chunks: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    a = a_ref[h]                                 # ()  decay rate (SMEM)
    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, pl.ds(h, 1), :].astype(jnp.float32)   # (1, Q) this head
    bm = b_ref[0].astype(jnp.float32)            # (Q, N)
    cm = c_ref[0].astype(jnp.float32)            # (Q, N)

    # Every vector stays 2-D (rows x lanes), as Mosaic lays them out: the
    # inclusive cumsum of the per-step log decay is a masked reduction
    # over a (Q, Q) tile, taken once along lanes (a column) and once
    # along sublanes (a row); the column of dt is the diagonal's.
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = iota_i >= iota_j
    la = dt * a                                            # (1, Q)
    dt_col = jnp.sum(jnp.where(iota_i == iota_j, dt, 0.0), axis=1,
                     keepdims=True)                        # (Q, 1)
    cs_col = jnp.sum(jnp.where(lower, la, 0.0), axis=1,
                     keepdims=True)                        # (Q, 1)
    cs_row = jnp.sum(jnp.where(iota_i <= iota_j, dt_col * a, 0.0), axis=0,
                     keepdims=True)                        # (1, Q)
    # intra-chunk quadratic form
    seg = cs_col - cs_row                                  # (Qi, Qj)
    decay = jnp.where(lower, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))   # (Qi, Qj)
    w = cb * decay * dt
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())))      # (Qi, P)
    # inter-chunk contribution from the carried state
    s = s_ref[...]                                               # (P, N)
    y += jnp.exp(cs_col) * jax.lax.dot_general(
        cm, s, (((1,), (1,)), ((), ())))                         # (Q, P)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update
    tot = cs_row[:, chunk - 1:]                                  # (1, 1)
    rem = jnp.exp(tot - cs_col) * dt_col                         # (Q, 1)
    dbx = jax.lax.dot_general(x, bm * rem,
                              (((0,), (0,)), ((), ())))          # (P, N)
    s_ref[...] = s * jnp.exp(tot) + dbx

    @pl.when(ci == num_chunks - 1)
    def _finish():
        s_out_ref[0, 0] = s_ref[...].astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, *, chunk: int = 128, interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); a: (H,); b, c: (B, L, N).

    Returns y (B, L, H, P) and final state (B, H, P, N) in float32."""
    B, L, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, L)
    nc = -(-L // chunk)
    pad = nc * chunk - L
    xt = x.transpose(0, 2, 1, 3)                 # (B, H, L, P)
    dtt = dt.transpose(0, 2, 1)                  # (B, H, L)
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dtt = jnp.pad(dtt, ((0, 0), (0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    grid = (B, H, nc)
    y, s = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                         # a
            pl.BlockSpec((1, 1, chunk, P), lambda bi, h, ci: (bi, h, ci, 0)),
            # dt: every head's row of the chunk (a (H, Q) tile); the
            # kernel picks its head's row — a (1, Q) block is no tile
            pl.BlockSpec((1, H, chunk), lambda bi, h, ci: (bi, 0, ci)),
            pl.BlockSpec((1, chunk, N), lambda bi, h, ci: (bi, ci, 0)),    # b
            pl.BlockSpec((1, chunk, N), lambda bi, h, ci: (bi, ci, 0)),    # c
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc * chunk, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(a, xt, dtt, b, c)
    return y[:, :, :L].transpose(0, 2, 1, 3), s
