"""The traffic generators: the same seed gives the same requests, another
seed the same lengths in another order, and each committed mix gives the
shape classes its cell warms."""
import numpy as np
import pytest

import chipbench_testkit  # noqa: F401  (puts the benchmark on the path)
from chipbench import registry, traffic

MIXES = sorted(p.stem for p in (registry.BENCH_DIR / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 1234567          # wider than 32 signed bits, as the driver's are


def _waves(mix_name, seed):
    mix = registry.traffic(mix_name)
    return mix, registry.generator(mix["kind"]).generate(mix, seed, 50_000, traffic)


@pytest.mark.parametrize("mix_name", MIXES)
def test_same_seed_same_requests(mix_name):
    mix, a = _waves(mix_name, BIG_SEED)
    _, b = _waves(mix_name, BIG_SEED)
    assert len(a) == mix["waves"] and all(len(w) == mix["clients"] for w in a)
    for wa, wb in zip(a, b):
        for ra, rb in zip(wa, wb):
            assert ra.max_new == rb.max_new
            np.testing.assert_array_equal(ra.prompt, rb.prompt)


@pytest.mark.parametrize("mix_name", MIXES)
def test_other_seed_same_lengths_other_order(mix_name):
    mix, a = _waves(mix_name, BIG_SEED)
    _, b = _waves(mix_name, BIG_SEED + 1)
    g = mix["max_batch"]
    for wa, wb in zip(a, b):
        for i in range(0, len(wa), g):
            la = sorted((len(r.prompt), r.max_new) for r in wa[i:i + g])
            lb = sorted((len(r.prompt), r.max_new) for r in wb[i:i + g])
            assert sorted(x for x, _ in la) == sorted(x for x, _ in lb)
            assert sorted(y for _, y in la) == sorted(y for _, y in lb)
    assert any(not np.array_equal(ra.prompt, rb.prompt) for ra, rb in zip(a[0], b[0]))
    ids = np.concatenate([r.prompt for r in a[0]])
    assert ids.min() >= traffic.FIRST_TOKEN_ID and ids.max() < 50_000


@pytest.mark.parametrize("mix_name,classes", [
    ("chat-c64-p1024", {(32, 1024, 128)}),
    ("chat-c128-p512", {(64, 512, 64)}),
])
def test_shape_classes(mix_name, classes):
    """Every wave of the mix needs the same programs: one class per group."""
    mix, waves = _waves(mix_name, BIG_SEED)
    entry = registry.entry(mix["entry"])
    seen = {c for w in waves for c in entry.shape_classes(w, mix["max_batch"])}
    assert seen == classes


@pytest.mark.parametrize("mix_name", MIXES)
def test_mix_names_its_source_and_padding(mix_name):
    """A committed mix names a public source for its shape and says in its
    own words whether a group pads."""
    mix = registry.traffic(mix_name)
    assert mix["source"].startswith("https://")
    fixed = mix["prompt"]["dist"] == "fixed"
    assert ("padding is excluded by construction" in mix["why"]) == fixed


def test_choice_deck_has_exact_shares():
    deck = traffic.length_deck({"dist": "choice", "values": [32, 64, 128],
                                "probs": [0.40, 0.35, 0.25]}, 32)
    assert [deck.count(v) for v in (32, 64, 128)] == [13, 11, 8]
    deck = traffic.length_deck({"dist": "choice", "values": [16, 32, 64],
                                "probs": [0.40, 0.35, 0.25]}, 64)
    assert [deck.count(v) for v in (16, 32, 64)] == [26, 22, 16]


def test_lognormal_deck_quantiles():
    dist = {"dist": "lognormal", "median": 384, "sigma": 0.6, "min": 128, "max": 1024}
    deck = traffic.length_deck(dist, 32)
    assert deck == sorted(deck) and deck[0] >= 128 and deck[-1] == 1024
    assert deck[15] <= 384 <= deck[16]          # the median sits mid-deck
