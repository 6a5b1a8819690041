"""Shared set-up of the benchmark's own tests: the benchmark's code on
the path, and a benchmark tree of test-sized cells whose configurations,
mixes and limits come from ``tests/data/bench`` and whose references,
metrics, entries and generators are the benchmark's own."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
SRC = BENCH.parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from chipbench import registry  # noqa: E402

CODE_DIRS = ("references", "metrics", "entries", "generators")
TEST_CELLS = [
    {"name": "tiny-dense.chat", "config": "tiny-dense", "traffic": "tiny-chat", "chips": 1},
    {"name": "tiny-mamba.chat", "config": "tiny-mamba", "traffic": "tiny-chat", "chips": 1},
    {"name": "tiny-dense.lognormal", "config": "tiny-dense", "traffic": "tiny-lognormal", "chips": 1},
    {"name": "tiny-dense.long", "config": "tiny-dense", "traffic": "tiny-long", "chips": 1},
    {"name": "tiny-mamba.long", "config": "tiny-mamba", "traffic": "tiny-long", "chips": 1},
]


def make_tree(tmp: Path) -> Path:
    """A benchmark directory under ``tmp``: the benchmark's code and the
    test data's files."""
    root = tmp / "bench"
    for d in CODE_DIRS:
        shutil.copytree(BENCH / d, root / d)
    for d in ("configs", "traffic", "limits"):
        shutil.copytree(DATA / "bench" / d, root / d)
    return root


def bench_with_test_cells() -> dict:
    bench = json.loads((registry.REPO / "BENCHMARK.json").read_text())
    bench["workloads"] = bench["workloads"] + TEST_CELLS
    return bench
