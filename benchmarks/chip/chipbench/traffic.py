"""Traffic: a mix file of parameters, read by the generator its ``kind``
names (``generators/<kind>.py``).

Every generator draws from the same helpers here, so that two seeds get
the same multiset of lengths in another order: lengths come from a
stratified deck per serving group (the quantiles of the stated
distribution, one per slot), and only their order and the token ids
depend on the seed.  The work a wave carries is then fixed by the mix,
and runs on different seeds differ by the system, not by the draw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

FIRST_TOKEN_ID = 2          # ids 0 and 1 are the program's pad and EOS


@dataclass(frozen=True)
class Request:
    prompt: np.ndarray      # int32 token ids
    max_new: int


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose; any non-negative whole seed,
    wider than 32 bits too."""
    tag = int.from_bytes(stream.encode(), "little") % (1 << 63)
    return np.random.default_rng([int(seed), tag])


def length_deck(dist: dict, n: int) -> list[int]:
    """``n`` lengths that stand for ``dist`` (sorted): its quantiles at
    (i + 0.5) / n, or exact shares for a choice of values."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["tokens"])] * n
    if kind == "choice":
        values, probs = dist["values"], dist["probs"]
        if abs(sum(probs) - 1.0) > 1e-9 or len(values) != len(probs):
            raise ValueError(f"bad choice distribution {dist}")
        raw = [p * n for p in probs]
        counts = [math.floor(r) for r in raw]
        # largest remainder: the shares sum to n exactly
        order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
        for i in order[:n - sum(counts)]:
            counts[i] += 1
        return sorted(v for v, c in zip(values, counts) for _ in range(c))
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        lo, hi = dist["min"], dist["max"]
        z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
        return sorted(min(hi, max(lo, round(math.exp(mu + sigma * zi)))) for zi in z)
    raise ValueError(f"unknown length distribution {kind!r}")


def group_requests(mix: dict, rng: np.random.Generator, vocab: int) -> list[Request]:
    """One serving group's requests: a shuffled prompt deck, an
    independently shuffled output deck, and uniform token ids in
    [FIRST_TOKEN_ID, vocab)."""
    n = mix["max_batch"]
    prompts = rng.permutation(length_deck(mix["prompt"], n))
    outputs = rng.permutation(length_deck(mix["output"], n))
    return [Request(rng.integers(FIRST_TOKEN_ID, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]
