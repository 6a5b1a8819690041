"""Elastic scaling drill: train -> lose half the slice -> re-plan -> resume.

Runs with 8 simulated devices (XLA host platform override, set before jax
imports).  A model trains on an 8-chip mesh, checkpoints, then the slice
"shrinks" to 4 chips: the planner re-solves the space/time trade-off, the
checkpoint is restored against the new mesh's shardings, and training
resumes — same data order, continuous loss.  This is the paper's core
motivation (automatic re-scaling instead of manual re-programming).

    PYTHONPATH=src python examples/elastic_rescale.py
"""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, "src")

import tempfile

import jax

from repro.configs.base import AttnCfg, ModelConfig, ShapeCfg
from repro.core import planner
from repro.launch.mesh import auto_mesh
from repro.runtime import TrainLoopConfig, train_loop
from repro.runtime.elastic import rescale


def main():
    cfg = ModelConfig(
        name="lm-elastic", family="dense", n_layers=2, d_model=128, d_ff=256,
        vocab=1024, block_pattern=(("attn", "dense"),),
        attn=AttnCfg(n_heads=4, n_kv_heads=2, head_dim=32),
        grad_accum=1, remat="none")
    shape = ShapeCfg("elastic", 64, 8, "train")
    ckpt = tempfile.mkdtemp(prefix="repro-elastic-")
    devs = jax.devices()
    print(f"{len(devs)} devices")

    # Phase 1: full slice (8 chips), planner-chosen layout
    p8 = planner.plan(cfg, shape, chips=8)
    ex8 = planner.to_execution(p8, cfg=cfg, chips=8)
    mesh8 = auto_mesh(ex8.mesh_shape, ex8.mesh_axes)
    print(f"phase 1: mesh {ex8.mesh_shape}  "
          f"(planned {p8.tokens_per_s:,.0f} tok/s)")
    s1 = train_loop(cfg, TrainLoopConfig(
        steps=20, seq_len=64, global_batch=8, ckpt_dir=ckpt, ckpt_interval=10,
        log_interval=5, warmup=5, tp=ex8.tp), mesh=mesh8)
    print(f"  steps {s1.steps_run}, loss {s1.final_loss:.4f}")

    # Phase 2: slice shrinks to 4 chips -> re-plan + reshard + resume
    r = rescale(cfg, shape, p8, new_chips=4, devices=devs[:4])
    print(f"phase 2: {r.summary()}")
    s2 = train_loop(cfg, TrainLoopConfig(
        steps=40, seq_len=64, global_batch=8, ckpt_dir=ckpt, ckpt_interval=10,
        log_interval=5, warmup=5, tp=r.execution.tp), mesh=r.mesh)
    print(f"  resumed from step {s2.restored_from}, "
          f"ran {s2.steps_run} more, loss {s2.final_loss:.4f}")

    # Phase 3: slice grows back to 8 -> re-plan again
    r2 = rescale(cfg, shape, r.plan, new_chips=8, devices=devs)
    print(f"phase 3: {r2.summary()}")
    s3 = train_loop(cfg, TrainLoopConfig(
        steps=60, seq_len=64, global_batch=8, ckpt_dir=ckpt, ckpt_interval=10,
        log_interval=5, warmup=5, tp=r2.execution.tp), mesh=r2.mesh)
    print(f"  resumed from step {s3.restored_from}, loss {s3.final_loss:.4f}")
    assert s3.final_step == 60


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
