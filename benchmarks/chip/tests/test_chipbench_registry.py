"""BENCHMARK.json names only files that exist, in the form the benchmark's
contract sets; and a configuration, a mix, a per-layer metric and an
entry are found by name once their files are added, with no file edited."""
import json
import re
import shutil

import pytest

import chipbench_testkit
from chipbench import registry

BENCH = json.loads((registry.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert (registry.REPO / BENCH["command"][1]).is_file()


def test_cells_name_existing_files():
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        spec = registry.config(c["name"])
        assert spec["name"] == c["name"] and spec["source"] == c["source"]
        assert spec["reduced"] == c["reduced"]
        registry.reference(spec["model"]["kind"]).layout(spec["model"])
    pairs = set()
    for cell in BENCH["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
        assert len(cell["why"]) <= 200
        pairs.add((cell["config"], cell["traffic"]))
        mix = registry.traffic(cell["traffic"])
        registry.generator(mix["kind"])
        registry.entry(mix["entry"])
        assert registry.limits(cell["name"])["widest_gap"]["limit"] > 0
    assert len(pairs) == len(BENCH["workloads"])


def test_metrics():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    cells = {c["name"] for c in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert callable(registry.metric(m["name"]).read)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert registry.metric(m["name"]).KERNEL == m["name"][:-len("_roofline")]
        layers.setdefault(m["layer"], set()).add(m["name"])
    # every cell has a whole-step share of the peak beside each kernel
    # roofline, moving the same end-to-end metric
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in n["name"].split(".") and n["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(n["workloads"])
                       for n in BENCH["per_layer"])


def test_files_added_are_found_by_name(tmp_path, monkeypatch):
    """A later change adds a configuration, a mix, a metric and an entry
    as new files; the registry finds each by its name."""
    root = chipbench_testkit.make_tree(tmp_path)
    shutil.copy(registry.BENCH_DIR / "configs" / "qwen2.5-3b.json", root / "configs" / "new-model.json")
    (root / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "closed_waves", "entry": "new_entry", "clients": 2, "max_batch": 2,
         "prompt": {"dist": "fixed", "tokens": 16},
         "output": {"dist": "fixed", "tokens": 4}, "waves": 2, "check_requests": 1}))
    (root / "metrics" / "new.layer_metric.py").write_text("def read(ctx):\n    return 42.0\n")
    shutil.copy(root / "entries" / "lmserver_waves.py", root / "entries" / "new_entry.py")
    monkeypatch.setattr(registry, "BENCH_DIR", root)
    assert registry.config("new-model")["model"]["n_layers"] == 36
    mix = registry.traffic("new-mix")
    waves = registry.generator(mix["kind"]).generate(mix, 5, 100, __import__("chipbench.traffic").traffic)
    assert [r.max_new for r in waves[0]] == [4, 4]
    assert registry.metric("new.layer_metric").read(None) == 42.0
    assert registry.entry("new_entry").Session is not None
    with pytest.raises(FileNotFoundError):
        registry.config("absent-model")
    with pytest.raises(ValueError):
        registry.traffic("../BENCHMARK")


@pytest.mark.parametrize("config", ["qwen2.5-3b", "mamba2-370m"])
def test_program_config_matches_the_file(config):
    """Every size the file states is checked against the program's field
    that the file names for it."""
    spec = registry.config(config)
    assert set(spec["model"]) - {"kind"} == set(spec["program"]["fields"])
    cfg = registry.entry("lmserver_waves").program_config(spec)
    assert cfg.n_layers == spec["model"]["n_layers"]


@pytest.mark.parametrize("change", ["field", "fixed", "unnamed"])
def test_program_config_refuses_a_departure(change):
    spec = json.loads(json.dumps(registry.config("qwen2.5-3b")))
    if change == "field":
        spec["model"]["d_ff"] += 1
    elif change == "fixed":
        spec["program"]["fixed"]["attn.window"] = 4096
    else:
        spec["model"]["new_width"] = 1
    with pytest.raises(ValueError):
        registry.entry("lmserver_waves").program_config(spec)
