"""A cell's weights drawn over its chips, and the reference run chip by
chip.  Over four virtual CPU devices, in one process of its own
(`chipbench_four_devices.py`): the draw equals the one-device draw bit
for bit with each device holding its block of rows, the chip-by-chip
reference gives the one pass's gaps bit for bit, a leaf four cannot
split is held whole or refused by name, and the 4-chip test cell runs
correct through `cli.run` with its stages on all four devices.  Over
one device, the draw is the one recorded before the split was added."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import chipbench_testkit
from chipbench import registry, weights

HERE = Path(__file__).resolve().parent
CONFIGS = ["tiny-dense-4l", "tiny-mamba-4l"]


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    scratch = tmp_path_factory.mktemp("four")
    res = subprocess.run([sys.executable, str(HERE / "chipbench_four_devices.py"), str(scratch)],
                         capture_output=True, text=True, timeout=600,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-3000:])
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("config", CONFIGS)
def test_four_device_draw_is_the_one_device_draw(four, config):
    got = four["configs"][config]
    assert got["draw_equal"]
    # each device holds one period of every layers leaf, and a quarter of
    # the embedding's rows
    assert got["layers_shards"] == [[d, 1] for d in range(4)]
    assert got["embed_rows"] == [512 // 4]


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_chip_by_chip_is_the_one_pass(four, config):
    gaps = four["configs"][config]["gaps"]
    assert not gaps["one_pass"]["by_chip"] and gaps["by_chip"]["by_chip"]
    assert gaps["by_chip"]["served"] == gaps["one_pass"]["served"]
    assert gaps["by_chip"]["int8"] == gaps["one_pass"]["int8"]
    assert gaps["one_pass"]["served"]["widest_gap"] > 0


def test_unsplittable_leaf_whole_or_refused_by_name(four):
    got = four["unsplittable"]
    assert got["odd_shards"] == [[5, 3]]
    assert got["refused"] is not None and "big/w" in got["refused"]


def test_four_chip_cell_runs_correct(four):
    out = four["cell"]["result"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 4
    assert out["device"]["count"] == 4
    assert out["checks"]["widest_gap"]["value"] <= out["checks"]["widest_gap"]["limit"]
    assert four["cell"]["stage_devices"] == [0, 1, 2, 3]


def test_one_device_draw_is_the_recorded_one(tmp_path, monkeypatch):
    """The tiny Mamba2 tree drawn over one device, leaf by leaf, against
    the checksums recorded from the draw as it was before it could split."""
    monkeypatch.setattr(registry, "BENCH_DIR", chipbench_testkit.make_tree(tmp_path))
    want = json.loads((HERE / "data" / "tiny-mamba.weights.json").read_text())
    m = registry.config("tiny-mamba")["model"]
    tree = weights.make(registry.reference(m["kind"]).layout(m), want["seed"], jax.devices()[:1])
    got = {"/".join(k.key for k in path): hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()
           for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == want["sha256"]


def test_draw_refuses_without_partitionable_keys():
    layout = {("w",): ((2, 2), "float32", ("uniform", 0.0, 1.0))}
    with jax.threefry_partitionable(False), pytest.raises(RuntimeError, match="partitionable"):
        weights.make(layout, 1, jax.devices()[:1])
