"""The harness over four virtual CPU devices, in a process of its own
(the device count is fixed when JAX starts):

    python chipbench_four_devices.py <scratch dir>

Draws each 4-layer test configuration over one device and over four,
runs the reference in one pass and chip by chip, asks `weights.make` for
leaves it cannot split, and drives the 4-chip test cell through
`cli.run`.  Prints one JSON line of what it found, for
`test_chipbench_chips.py` to judge."""
import json
import os
import sys
import time
from pathlib import Path

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import chipbench_testkit  # noqa: E402
from chipbench import check, cli, registry, weights  # noqa: E402

SEED = 2**31 + 99
CONFIGS = ("tiny-dense-4l", "tiny-mamba-4l")


def _inputs(m: dict):
    """Two blocks of reference rows: prompts and served tokens at random
    ids, the second block's last rows reading fewer positions."""
    rng = np.random.default_rng(SEED)
    rows, seq, reads = 2 * check.BLOCK, 40, 6
    tokens = rng.integers(0, m["vocab"], (rows, seq)).astype(np.int32)
    positions = np.sort(rng.integers(0, seq, (rows, reads)), axis=1).astype(np.int32)
    served = rng.integers(0, m["vocab"], (rows, reads)).astype(np.int32)
    served[-3:, 3:] = -1
    return tokens, positions, served


def draws_and_references(devices) -> dict:
    out = {}
    for name in CONFIGS:
        m = registry.config(name)["model"]
        ref = registry.reference(m["kind"])
        one = weights.make(ref.layout(m), SEED, devices[:1])
        four = weights.make(ref.layout(m), SEED, devices)
        pairs = list(zip(jax.tree.leaves(one), jax.tree.leaves(four)))
        inputs = _inputs(m)
        got = {}
        for how, params in (("one_pass", one), ("by_chip", four)):
            got[how] = {"served": check.served_gaps(ref, params, m, inputs),
                        "int8": check.control_gaps(ref, params, m, inputs, "int8"),
                        "by_chip": params is four}
        out[name] = {
            "draw_equal": all(a.dtype == b.dtype and np.array_equal(a, b) for a, b in pairs),
            "layers_shards": sorted({(s.device.id, s.data.shape[0])
                                     for leaf in jax.tree.leaves(four["layers"])
                                     for s in leaf.addressable_shards}),
            "layers_leaves": len(jax.tree.leaves(four["layers"])),
            "embed_rows": sorted({s.data.shape[0] for s in four["embed"].addressable_shards}),
            "gaps": got,
        }
    return out


def unsplittable(devices) -> dict:
    """A small leaf 4 does not divide is held whole; a large one is refused
    by name, from its shape alone (a draw of it would take 160 MiB)."""
    small = {("layers", "w"): ((4, 8, 8), "bfloat16", ("fan_in",)),
             ("odd",): ((5, 3), "float32", ("uniform", 0.0, 1.0))}
    tree = weights.make(small, SEED, devices)
    big = dict(small)
    big[("big", "w")] = ((5, 2**13, 2**10), "float32", ("uniform", 0.0, 1.0))
    try:
        weights.make(big, SEED, devices)
        refused = None
    except ValueError as e:
        refused = str(e)
    return {"odd_shards": sorted({s.data.shape for s in tree["odd"].addressable_shards}),
            "refused": refused}


def four_chip_cell(devices, root: Path) -> dict:
    """`cli.run` of the 4-chip test cell, with the stage devices its
    server placed."""
    registry.BENCH_DIR = chipbench_testkit.make_tree(root)
    bench = chipbench_testkit.bench_with_test_cells()
    cell = next(c for c in bench["workloads"] if c["name"] == "tiny-dense-4l.chat")
    session = registry.entry("lmserver_waves").Session
    placed = []
    init = session.__init__

    def spy(self, *a, **kw):
        init(self, *a, **kw)
        placed.extend(d.id for devs in self.pipe.stage_devices for d in devs)
    session.__init__ = spy
    out = cli.run(bench, cell, SEED, 1.0, False, devices[:cell["chips"]], time.perf_counter())
    return {"result": out, "stage_devices": sorted(set(placed))}


def main(scratch: str) -> None:
    devices = jax.devices()
    assert len(devices) == 4, devices
    registry.BENCH_DIR = chipbench_testkit.make_tree(Path(scratch) / "draws")
    found = {"configs": draws_and_references(devices), "unsplittable": unsplittable(devices)}
    found["cell"] = four_chip_cell(devices, Path(scratch) / "cell")
    print(json.dumps(found))


if __name__ == "__main__":
    main(sys.argv[1])
