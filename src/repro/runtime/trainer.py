"""Fault-tolerant training loop.

Layers (bottom-up): data pipeline -> jitted train step (launch.steps) ->
checkpointing (async, atomic) -> failure handling.  ``train_loop`` runs
one incarnation of the job; ``run_resilient`` is the job-controller
contract: restart incarnations from the last committed checkpoint until
the step budget is met (exactly what a pod-scale controller does after a
node failure — here in-process so it is testable in CI).

Determinism contract: data batch ``i`` is a pure function of (seed, i), so
a restart replays the exact token stream from the restored step; training
curves across failures are bitwise-reproducible on the same topology.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from .. import sharding_ctx as sctx
from ..checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from ..configs.base import ModelConfig, ShapeCfg
from ..data import DataState, make_pipeline
from ..launch import sharding as shd
from ..launch.mesh import auto_mesh
from ..launch.steps import abstract_params, abstract_opt_state, make_train_step
from ..models import build_model
from .failures import FailureInjector
from .straggler import StragglerMonitor


def local_mesh(tp: int = 1):
    """Mesh over this process's devices: ("data", "model")."""
    n = len(jax.devices())
    assert n % tp == 0, f"{n} devices not divisible by tp={tp}"
    return auto_mesh((n // tp, tp), ("data", "model"))


@dataclass
class TrainLoopConfig:
    steps: int = 100
    seq_len: int = 128
    global_batch: int = 8
    grad_accum: int = 1
    lr: float = 3e-4
    warmup: int = 50
    seed: int = 0
    data_kind: str = "bigram"
    ckpt_dir: str | None = None
    ckpt_interval: int = 50
    keep: int = 3
    log_interval: int = 10
    restore: bool = True
    tp: int = 1
    fsdp: bool = False
    failures: FailureInjector | None = None
    straggler: StragglerMonitor | None = None
    on_metrics: Callable[[dict], None] | None = None
    metrics_path: str | None = None


@dataclass
class TrainSummary:
    steps_run: int
    final_step: int
    losses: dict[int, float] = field(default_factory=dict)
    straggler_events: int = 0
    restored_from: int | None = None
    checkpoints: list[int] = field(default_factory=list)

    @property
    def final_loss(self) -> float:
        return self.losses[max(self.losses)] if self.losses else float("nan")


def _writer(path: str | None):
    if path is None:
        return lambda rec: None
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    fh = p.open("a")

    def write(rec: dict):
        fh.write(json.dumps(rec) + "\n")
        fh.flush()
    return write


def train_loop(cfg: ModelConfig, loop: TrainLoopConfig, *,
               mesh=None) -> TrainSummary:
    """One incarnation: restore -> step until loop.steps or failure."""
    mesh = mesh if mesh is not None else local_mesh(loop.tp)
    shape = ShapeCfg("custom", loop.seq_len, loop.global_batch, "train")
    policy = shd.ShardingPolicy(fsdp=loop.fsdp, tp=loop.tp > 1)
    ctx = sctx.from_mesh(mesh)

    model, opt, step_fn = make_train_step(
        cfg, lr=loop.lr, warmup=loop.warmup, total_steps=loop.steps,
        grad_accum=loop.grad_accum)
    params_s = abstract_params(model)
    opt_s = abstract_opt_state(opt, params_s)
    param_sh = shd.tree_shardings(params_s, mesh, cfg, policy)
    opt_sh = shd.tree_shardings(opt_s, mesh, cfg, policy)

    pipe = make_pipeline(loop.data_kind, cfg, shape, seed=loop.seed,
                         accum=loop.grad_accum)
    data_state = pipe.init_state()

    start_step = 0
    restored_from = None
    if loop.restore and loop.ckpt_dir and latest_step(loop.ckpt_dir) is not None:
        like = {"params": params_s, "opt_state": opt_s,
                "step": jax.ShapeDtypeStruct((), np.int64),
                "data_step": jax.ShapeDtypeStruct((), np.int64)}
        tree, _meta = restore_checkpoint(loop.ckpt_dir, like)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), tree["params"], param_sh)
        opt_state = jax.tree.map(
            lambda x, s: jax.device_put(x, s), tree["opt_state"], opt_sh)
        start_step = int(tree["step"])
        data_state = DataState(step=int(tree["data_step"]), seed=loop.seed)
        restored_from = start_step
    else:
        with mesh, sctx.activate(ctx):
            params = jax.jit(model.init,
                             out_shardings=param_sh)(jax.random.PRNGKey(loop.seed))
            opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)

    batch_sh = None
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    write = _writer(loop.metrics_path)
    summary = TrainSummary(steps_run=0, final_step=start_step,
                           restored_from=restored_from)
    ckpt = AsyncCheckpointer(loop.ckpt_dir, keep=loop.keep) \
        if loop.ckpt_dir else None

    def save(step_i, params, opt_state, data_state):
        if ckpt is None:
            return
        ckpt.save(step_i, {
            "params": params, "opt_state": opt_state,
            "step": np.int64(step_i), "data_step": np.int64(data_state.step),
        }, metadata={"cfg": cfg.name})
        summary.checkpoints.append(step_i)

    try:
        if loop.straggler is not None:
            loop.straggler.new_incarnation()
        step_arr = np.int32(start_step)
        for i in range(start_step, loop.steps):
            batch = pipe.host_batch(data_state)
            if batch_sh is None:
                specs = shd.batch_specs(mesh, batch, accum=True)
                batch_sh = shd.named(mesh, specs)
            batch = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                 batch, batch_sh)
            t0 = time.perf_counter()
            if loop.failures is not None:
                loop.failures.maybe_fail(i)   # crash raises; stall is timed
            with mesh, sctx.activate(ctx):
                params, opt_state, metrics = jitted(
                    params, opt_state, step_arr, batch)
            loss = float(metrics["loss"])            # blocks = step barrier
            dt = time.perf_counter() - t0
            if loop.straggler is not None:
                loop.straggler.observe(i, dt)
            data_state = data_state.advance()
            step_arr = np.int32(i + 1)
            summary.steps_run += 1
            summary.final_step = i + 1
            if i % loop.log_interval == 0 or i == loop.steps - 1:
                summary.losses[i] = loss
                rec = {"step": i, "loss": loss, "sec": round(dt, 4)}
                write(rec)
                if loop.on_metrics is not None:
                    loop.on_metrics(rec)
            if loop.ckpt_interval and (i + 1) % loop.ckpt_interval == 0:
                save(i + 1, params, opt_state, data_state)
        if loop.ckpt_interval and loop.steps % loop.ckpt_interval != 0:
            save(loop.steps, params, opt_state, data_state)
    finally:
        if ckpt is not None:
            ckpt.close()
        if loop.straggler is not None:
            summary.straggler_events = len(loop.straggler.events)
    return summary


def run_resilient(cfg: ModelConfig, loop: TrainLoopConfig, *,
                  max_restarts: int = 3, mesh=None) -> dict:
    """The job-controller contract: restart from the last committed
    checkpoint on (simulated) node failure, up to ``max_restarts``."""
    from .failures import SimulatedNodeFailure

    assert loop.ckpt_dir, "resilient training requires a checkpoint dir"
    incarnations: list[TrainSummary] = []
    restarts = 0
    while True:
        try:
            s = train_loop(cfg, loop, mesh=mesh)
            incarnations.append(s)
            break
        except SimulatedNodeFailure:
            restarts += 1
            if restarts > max_restarts:
                raise
            # next incarnation restores from the last committed step
            continue
    total_steps = sum(s.steps_run for s in incarnations)
    return {
        "restarts": restarts,
        "incarnations": len(incarnations),
        "total_steps_run": total_steps,
        "final_step": incarnations[-1].final_step,
        "final_loss": incarnations[-1].final_loss,
        "losses": {k: v for s in incarnations for k, v in s.losses.items()},
        "summaries": incarnations,
    }
