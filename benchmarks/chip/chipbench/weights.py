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

Over one device the tree is drawn there.  Over N devices it is drawn in
one jitted call whose outputs are split over a one-axis mesh of them:
every leaf along its first axis into N equal row blocks, block i on
device i.  Device i so holds periods [iL/N, (i+1)L/N) of each ``layers``
leaf, as an even N-stage pipeline would, and a 1/N block of the rows of
every other leaf (``embed``, an untied ``head``).  A leaf whose first
axis N does not divide is held whole on every device where it is under
`REPLICATE_MAX` bytes, and refused, by name, before anything is drawn
where it is larger.  The values do not depend on N: JAX's
``jax_threefry_partitionable`` draws each element from its own counter,
whatever the split, and `make` refuses to draw without it.  Where the
program wants its weights is the program's business; this is where the
harness puts them.
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


REPLICATE_MAX = 64 * 2**20     # bytes: a leaf N cannot split is held whole below this


def _row_split(layout: dict, n: int) -> dict:
    """``{path: True}`` where a leaf is split into ``n`` row blocks and
    ``False`` where it is held whole; raises for a leaf too large to hold
    whole.  Reads shapes only."""
    split = {}
    for path, (shape, dtype, _) in layout.items():
        if shape and shape[0] % n == 0:
            split[path] = True
            continue
        size = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if size >= REPLICATE_MAX:
            raise ValueError(f"leaf {'/'.join(path)} {tuple(shape)} ({size} bytes): its first "
                             f"axis does not split into {n} row blocks, and it is too large "
                             f"to hold whole on every device")
        split[path] = False
    return split


def make(layout: dict, seed: int, devices):
    """The nested dict tree of ``layout``, drawn from ``seed`` over
    ``devices``: whole on one device, split by rows over several."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("drawing weights needs jax_threefry_partitionable: without it "
                           "a leaf's values would depend on how it is split")
    devices = list(devices)
    split = _row_split(layout, len(devices)) if len(devices) > 1 else None
    paths = sorted(layout)

    def nest(leaf_of):
        tree: dict = {}
        for path in paths:
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf_of(path)
        return tree

    def build(key):
        def leaf(path):
            shape, dtype, init = layout[path]
            k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
            return _leaf(k, tuple(shape), dtype, init)
        return nest(leaf)

    if split is None:
        key = jax.device_put(seed_key(seed), devices[0])
        return jax.block_until_ready(jax.jit(build)(key))
    mesh = Mesh(np.asarray(devices), ("chip",))
    whole = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec("chip"))
    out = nest(lambda path: rows if split[path] else whole)
    key = jax.device_put(seed_key(seed), whole)
    return jax.block_until_ready(jax.jit(build, out_shardings=out)(key))


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
