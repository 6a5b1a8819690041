"""Whether what the timed path served is what the model computes.

Once the window has closed, a sample of the finished requests, drawn
from the seed and holding the longest one, is run through the plain
float32 reference of the configuration in one pass over each prompt and
its served tokens.  The number compared is the widest gap by which a
served token's reference logit lies below the reference's best logit at
that position: greedy serving at the configuration's precision keeps it
to rounding, a wrong token or a wrong state makes it large.

The control is the same reference computed in a lower precision: at the
same positions, the token it puts first is read against the float32
logits the same way.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import traffic

BLOCK = 8        # rows per reference call, so that its memory is a block's


@dataclass(frozen=True)
class Served:
    prompt: np.ndarray       # int32
    tokens: list             # the served (generated) token ids


def pick(done: list[Served], n: int, seed: int) -> list[Served]:
    """``n`` finished requests: the one with the most served tokens (the
    first such), and the rest drawn from the seed."""
    if not done:
        raise ValueError("no finished request to check")
    longest = max(range(len(done)), key=lambda i: (len(done[i].tokens), -i))
    rest = [i for i in range(len(done)) if i != longest]
    rng = traffic.rng_for(seed, "check")
    drawn = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(drawn)]


def batch(sample: list[Served], seq: int, reads: int):
    """Fixed-shape inputs for the reference: each prompt and its served
    tokens but the last, right-padded to ``seq``; the positions whose
    logits chose each served token, and those tokens, padded to
    ``reads`` with -1.  Rows are padded to a whole number of blocks with
    rows that read nothing."""
    B = -(-len(sample) // BLOCK) * BLOCK
    tokens = np.full((B, seq), traffic.FIRST_TOKEN_ID, np.int32)
    positions = np.zeros((B, reads), np.int32)
    served = np.full((B, reads), -1, np.int32)
    for i, s in enumerate(sample):
        n, p = len(s.tokens), len(s.prompt)
        if p + n - 1 > seq or n > reads:
            raise ValueError(f"request of {p} + {n} tokens exceeds the check's "
                             f"{seq} positions / {reads} reads")
        row = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
        tokens[i, :len(row)] = row
        positions[i, :n] = np.arange(p - 1, p - 1 + n)
        served[i, :n] = s.tokens
    return tokens, positions, served


def _gap_stats(gaps, served) -> dict:
    """The numbers a check may compare, over the positions read: the
    widest gap, the mean gap, and the share of served tokens that are
    not the float32 reference's best."""
    g = np.asarray(gaps, np.float64)[np.asarray(served) >= 0]
    return {"widest_gap": float(g.max()), "mean_gap": float(g.mean()),
            "miss_share": float(np.mean(g > 0))}


def _blocks(ref, params, m: dict, inputs, probes, quant=None):
    """`ref.gaps` over the rows of ``inputs``, one block at a time."""
    tokens, positions, served = inputs
    outs = [ref.gaps(params, m, tokens[i:i + BLOCK], positions[i:i + BLOCK],
                     served[i:i + BLOCK], probes[i:i + BLOCK], quant=quant)
            for i in range(0, len(tokens), BLOCK)]
    return tuple(np.concatenate([np.asarray(o[k]) for o in outs]) for k in range(3))


def served_gaps(ref, params, m: dict, inputs) -> dict:
    """How far the served tokens lie below the float32 reference's best
    logit (`_gap_stats`)."""
    served = inputs[2]
    gap_served, _, _ = _blocks(ref, params, m, inputs, served)
    return _gap_stats(gap_served, served)


def control_gaps(ref, params, m: dict, inputs, quant: str) -> dict:
    """The same numbers, in the float32 reference's logits, for the tokens
    that the reference computed in ``quant`` puts first."""
    served = inputs[2]
    _, _, first = _blocks(ref, params, m, inputs, served, quant)
    probes = np.where(served >= 0, first, -1).astype(np.int32)
    _, gap_probe, _ = _blocks(ref, params, m, inputs, probes)
    return _gap_stats(gap_probe, served)


def verdict(limits: dict, numbers: dict, failed: int) -> tuple[bool, dict]:
    """Whether a run is correct: each number its limits file names at or
    under its limit, and no request short.  Returns the verdict and every
    number compared beside its limit."""
    checks = {name: {"value": numbers[name], "limit": spec["limit"]}
              for name, spec in limits.items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
