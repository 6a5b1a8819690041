"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

Demonstrates the full substrate on real (synthetic-bigram) data: sharded
deterministic pipeline -> jitted train step (grad accumulation + remat) ->
async atomic checkpoints -> a mid-run injected node failure with automatic
restart -> loss convergence toward the data entropy floor (ln 4 ≈ 1.386).

    PYTHONPATH=src python examples/train_lm.py            # ~100M, 300 steps
    PYTHONPATH=src python examples/train_lm.py --small    # CI-sized
"""
import argparse
import sys
import tempfile

sys.path.insert(0, "src")

from repro.configs.base import AttnCfg, ModelConfig
from repro.runtime import (FailureInjector, StragglerMonitor,
                           TrainLoopConfig, run_resilient)


def model_100m() -> ModelConfig:
    """12L d=640 GQA ff=1920 vocab=32768 — ~99M params."""
    return ModelConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=640, d_ff=1920,
        vocab=32_768, block_pattern=(("attn", "dense"),),
        attn=AttnCfg(n_heads=10, n_kv_heads=2, head_dim=64),
        act="silu_glu", grad_accum=1, remat="none")


def model_small() -> ModelConfig:
    return ModelConfig(
        name="lm-small", family="dense", n_layers=2, d_model=128, d_ff=384,
        vocab=2048, block_pattern=(("attn", "dense"),),
        attn=AttnCfg(n_heads=4, n_kv_heads=2, head_dim=32),
        act="silu_glu", grad_accum=1, remat="none")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = model_small() if args.small else model_100m()
    steps = args.steps or (60 if args.small else 300)
    n_params = cfg.param_count()
    print(f"model {cfg.name}: {n_params/1e6:.1f}M params, {steps} steps")

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro-train-")
    loop = TrainLoopConfig(
        steps=steps,
        seq_len=64 if args.small else 128,
        global_batch=8 if args.small else 4,
        lr=1e-3, warmup=max(10, steps // 10),
        data_kind="bigram",                    # entropy floor = ln(4)
        ckpt_dir=ckpt_dir, ckpt_interval=max(10, steps // 6),
        log_interval=max(1, steps // 15),
        failures=FailureInjector({steps // 2: "crash"}),   # mid-run node loss
        straggler=StragglerMonitor(),
        on_metrics=lambda r: print(
            f"  step {r['step']:5d}  loss {r['loss']:.4f}  "
            f"{r['sec']*1e3:9.1f} ms"))

    out = run_resilient(cfg, loop, max_restarts=2)
    first = min(out["losses"])
    print(f"\nrestarts (injected node failure): {out['restarts']}")
    print(f"loss: {out['losses'][first]:.3f} -> {out['final_loss']:.3f} "
          f"(data entropy floor ~1.386)")
    print(f"checkpoints under {ckpt_dir}")
    assert out["final_loss"] < out["losses"][first], "no learning happened?!"


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
