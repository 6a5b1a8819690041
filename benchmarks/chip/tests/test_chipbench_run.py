"""Whole runs of test-sized cells on the CPU, past the harness's look for
a chip: a sound run comes out correct, and a run whose timed path is
broken underneath comes out not correct (a token altered where it is
produced; a decode step that hands its state back unchanged; prompts
left-padded inside a group).  And the command itself refuses to run
without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import chipbench_testkit
from chipbench import cli, registry

SEED = 2**31 + 99


@pytest.fixture
def bench(tmp_path, monkeypatch):
    monkeypatch.setattr(registry, "BENCH_DIR", chipbench_testkit.make_tree(tmp_path))
    return chipbench_testkit.bench_with_test_cells()


def _run(bench, name, seconds=1.0):
    cell = next(c for c in bench["workloads"] if c["name"] == name)
    return cli.run(bench, cell, SEED, seconds, False, jax.devices()[:1], time.perf_counter())


@pytest.mark.parametrize("name", ["tiny-dense.chat", "tiny-mamba.chat"])
def test_sound_run_is_correct(bench, name):
    out = _run(bench, name)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 4
    assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms", "ttft_p90_ms",
                                   "peak_hbm_gb", "setup_s"}
    assert out["metrics"]["output_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["widest_gap"]["value"] <= out["checks"]["widest_gap"]["limit"]


@pytest.mark.parametrize("name", ["tiny-dense.long", "tiny-mamba.long"])
def test_altered_token_is_not_correct(bench, name, monkeypatch):
    from repro.runtime.pipeline import decode
    sample = decode.DecodePipeline._sample

    def altered(self, logits, gid, temperature=None):
        return (sample(self, logits, gid, temperature) + 1) % logits.shape[-1]
    monkeypatch.setattr(decode.DecodePipeline, "_sample", altered)
    out = _run(bench, name)
    assert not out["correct"]
    assert out["checks"]["widest_gap"]["value"] > out["checks"]["widest_gap"]["limit"]


@pytest.mark.parametrize("name", ["tiny-dense.long", "tiny-mamba.long"])
def test_state_left_unchanged_is_not_correct(bench, name, monkeypatch):
    from repro.models import lm
    decode_blocks = lm.decode_blocks

    def stale(cfg, params, cache, x, pos, **kw):
        h, _ = decode_blocks(cfg, params, cache, x, pos, **kw)
        return h, cache
    monkeypatch.setattr(lm, "decode_blocks", stale)
    out = _run(bench, name)
    assert not out["correct"]


def test_left_padded_prompts_are_not_correct(bench):
    """The server pads a group's shorter prompts on the left with token 0
    and does not mask the padding; served against the plain reference of
    each prompt alone, a dense model's tokens come out wrong."""
    out = _run(bench, "tiny-dense.lognormal")
    assert not out["correct"]


def test_no_tpu_no_result(capsys, monkeypatch):
    # the command points JAX's cache at the checkout: undo that afterwards
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    monkeypatch.setattr(jax.config, "update", lambda *a: None)
    rc = cli.main(["--workload", "mamba2-370m.chat-p512", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == cli.NO_CHIP
    assert capsys.readouterr().out == ""


def test_bare_checkout_fails(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    exits with an error and prints no result."""
    shutil.copy(registry.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(registry.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                          "mamba2-370m.chat-p512", "--seed", "1", "--seconds", "1",
                          "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert res.returncode != 0
    assert not any(line.startswith("{") for line in res.stdout.splitlines())
    with pytest.raises(json.JSONDecodeError):
        json.loads(res.stdout.strip().splitlines()[-1] if res.stdout.strip() else "")
