"""Weights drawn from the seed, on the device, in one jitted call.

A reference module states its model's parameter tree as a layout:
``{path: (shape, dtype, init)}``, where ``path`` is a tuple of dict keys
and ``init`` one of

  ``("normal", std)``          truncated normal in [-2, 2] std, times std
  ``("fan_in",)``              the same with std = shape[-2] ** -0.5
  ``("ones_noise", std)``      1 + std * normal (norm gains)
  ``("uniform", lo, hi)``      uniform in [lo, hi)
  ``("log_uniform", lo, hi)``  log of a uniform draw in [lo, hi)
  ``("dt_bias", lo, hi)``      inverse softplus of a log-uniform draw in
                               [lo, hi) (Mamba2's time-step bias)

Each leaf has a key of its own, folded from the seed and its path, so a
leaf's values do not depend on which other leaves a layout holds.
"""
from __future__ import annotations

import zlib

import numpy as np


def seed_key(seed: int):
    """A JAX key from any non-negative whole seed, 64 bits wide."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _leaf(key, shape, dtype, init):
    """One leaf, drawn in its own dtype where it is a matrix draw, so that
    drawing the weights holds no float32 copy of them."""
    import jax
    import jax.numpy as jnp
    kind = init[0]
    if kind in ("normal", "fan_in"):
        std = init[1] if kind == "normal" else shape[-2] ** -0.5
        v = jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) * jnp.asarray(std, dtype)
    elif kind == "ones_noise":
        v = 1.0 + init[1] * jax.random.normal(key, shape, jnp.float32)
    elif kind == "uniform":
        v = jax.random.uniform(key, shape, dtype, init[1], init[2])
    elif kind == "log_uniform":
        v = jnp.log(jax.random.uniform(key, shape, jnp.float32, init[1], init[2]))
    elif kind == "dt_bias":
        lo, hi = np.log(init[1]), np.log(init[2])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        v = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init {init!r}")
    return v.astype(dtype)


def make(layout: dict, seed: int, device):
    """The nested dict tree of ``layout``, drawn from ``seed`` on ``device``."""
    import jax
    paths = sorted(layout)

    def build(key):
        tree: dict = {}
        for path in paths:
            shape, dtype, init = layout[path]
            k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = _leaf(k, tuple(shape), dtype, init)
        return tree

    key = jax.device_put(seed_key(seed), device)
    return jax.block_until_ready(jax.jit(build)(key))


def layout_of(tree) -> dict:
    """``{path: (shape, dtype name)}`` of a nested dict tree of arrays or
    shape structs, for comparing a program's tree with a layout."""
    out = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
        else:
            out[path] = (tuple(node.shape), np.dtype(node.dtype).name)
    walk(tree, ())
    return out
