"""Everything that belongs to one configuration, traffic mix, per-layer
metric, client entry, reference or cell sits in a file of its own,
found here by the name ``BENCHMARK.json`` gives it.  Adding one is
adding files; no file here changes."""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
REPO = BENCH_DIR.parents[1]
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _path(kind: str, name: str, suffix: str) -> Path:
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = BENCH_DIR / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return path


def _json(kind: str, name: str) -> dict:
    return json.loads(_path(kind, name, ".json").read_text())


def _module(kind: str, name: str):
    path = _path(kind, name, ".py")
    mod_name = f"chipbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def limits(cell: str) -> dict:
    return _json("limits", cell)


def generator(kind: str):
    return _module("generators", kind)


def metric(name: str):
    return _module("metrics", name)


def entry(name: str):
    return _module("entries", name)


def reference(kind: str):
    return _module("references", kind)
