"""Closed-loop waves: ``clients`` callers, each sending its next request
once its reply is back.  Through a server that takes a whole list and
returns when all of it is done, that is a wave of ``clients`` requests
at a time, handed over in this order.  Every wave is made up front."""
from __future__ import annotations


def generate(mix: dict, seed: int, vocab: int, traffic) -> list[list]:
    clients, group = mix["clients"], mix["max_batch"]
    if clients % group:
        raise ValueError(f"{clients} clients do not fill groups of {group}")
    rng = traffic.rng_for(seed, "traffic")
    return [[r for _ in range(clients // group)
             for r in traffic.group_requests(mix, rng, vocab)]
            for _ in range(mix["waves"])]
