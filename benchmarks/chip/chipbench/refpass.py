"""The plain reference over a drawn tree, in the three pieces a reference
module gives: ``embed_in(params, tokens, m)``, ``layer_stack(layers, x,
m, quant)`` and ``readout(params, x, positions, served, probes, m,
quant)``.

Where the tree's ``layers`` stack is whole on each device that holds it,
`one_pass` runs the three in one jitted call.  Where `weights.make` has
split it over several chips, `by_chip` runs ``embed_in`` over the tree's
devices, then ``layer_stack`` on each chip over that chip's own block of
periods, in period order, with the activation moved there, then
``readout`` over the devices again, with the row-split embedding or
head: no chip holds more than its share of the weights and what one
layer computes.

Imports JAX as it is imported, unlike the harness's other modules: it is
imported only once a run has set JAX up.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec

from .weights import REPLICATE_MAX


def _key(m: dict) -> tuple:
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnames=("ref", "m", "quant"))
def _one_pass(params, tokens, positions, served, probes, *, ref, m, quant):
    m = dict(m)
    x = ref.layer_stack(params["layers"], ref.embed_in(params, tokens, m), m, quant)
    return ref.readout(params, x, positions, served, probes, m, quant)


@functools.partial(jax.jit, static_argnames=("ref", "m"))
def _embed_in(params, tokens, *, ref, m):
    return ref.embed_in(params, tokens, dict(m))


@functools.partial(jax.jit, static_argnames=("ref", "m", "quant"))
def _layer_stack(layers, x, *, ref, m, quant):
    return ref.layer_stack(layers, x, dict(m), quant)


@functools.partial(jax.jit, static_argnames=("ref", "m", "quant"))
def _readout(params, x, positions, served, probes, *, ref, m, quant):
    return ref.readout(params, x, positions, served, probes, dict(m), quant)


def one_pass(ref, params, m: dict, tokens, positions, served, probes, quant=None):
    """Per read position (B, K): the served token's and the probe token's
    distance below the best logit, and the best token, for the sequences
    ``tokens`` (B, T) read at ``positions`` (B, K)."""
    return _one_pass(params, tokens, positions, served, probes,
                     ref=ref, m=_key(m), quant=quant)


def is_split(layers) -> bool:
    """Whether the drawn ``layers`` stack is split over devices."""
    return any(not leaf.sharding.is_fully_replicated for leaf in jax.tree.leaves(layers))


def stack_blocks(layers) -> list:
    """``(device, layers block)`` for each device's own block of periods of
    a ``layers`` stack split by `weights.make`, in period order."""
    leaves, treedef = jax.tree.flatten(layers)
    shards = [sorted(leaf.addressable_shards, key=lambda s: s.index[0].start)
              for leaf in leaves]
    return [(block[0].device, treedef.unflatten([s.data for s in block]))
            for block in zip(*shards)]


def by_chip(ref, params, m: dict, tokens, positions, served, probes, quant=None):
    """As `one_pass`, chip by chip over a split ``layers`` stack."""
    rest = {k: v for k, v in params.items() if k != "layers"}
    whole = NamedSharding(jax.tree.leaves(rest)[0].sharding.mesh, PartitionSpec())
    # a small leaf, as a norm's gains, whole on every chip: a reduction
    # over it is then not split across chips, and sums in one pass's order
    rest = jax.tree.map(lambda a: a if a.nbytes >= REPLICATE_MAX else jax.device_put(a, whole),
                        rest)
    key = _key(m)
    x = _embed_in(rest, tokens, ref=ref, m=key)
    for dev, block in stack_blocks(params["layers"]):
        x = _layer_stack(block, jax.device_put(x, dev), ref=ref, m=key, quant=quant)
    x = jax.device_put(x, whole)
    return _readout(rest, x, positions, served, probes, ref=ref, m=key, quant=quant)
