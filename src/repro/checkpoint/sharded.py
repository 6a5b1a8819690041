"""Atomic sharded checkpoints with async save and retention.

Commit protocol (multi-host safe by construction):
  1. every process writes its addressable shards into ``<dir>/.tmp-<step>-<nonce>/shard-{proc:05d}.npz``
  2. barrier (no-op single-process; ``jax.experimental.multihost_utils``
     at scale)
  3. process 0 writes ``meta.json`` (tree paths, shapes, dtypes, step,
     n_processes, user metadata), then atomically ``rename``s the tmp dir
     to ``step-<step>``.  A checkpoint directory is valid iff the rename
     happened, so readers can never observe a torn checkpoint.
  4. retention: keep the newest ``keep`` steps (plus any step in
     ``keep_every`` milestones), delete the rest.

Restore validates path-set/shape/dtype against a ``like`` pytree (from
``jax.eval_shape``) and device_puts against target shardings when given —
this is also the resharding path used by elastic rescale (restore the same
checkpoint under a different mesh).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from pathlib import Path

import jax
import numpy as np

_STEP_PREFIX = "step-"


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _flatten(tree) -> dict[str, np.ndarray]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_path_str(p): np.asarray(l) for p, l in leaves}


def list_steps(ckpt_dir: str | os.PathLike) -> list[int]:
    d = Path(ckpt_dir)
    if not d.exists():
        return []
    out = []
    for p in d.iterdir():
        if p.is_dir() and p.name.startswith(_STEP_PREFIX):
            try:
                out.append(int(p.name[len(_STEP_PREFIX):]))
            except ValueError:
                continue
    return sorted(out)


def latest_step(ckpt_dir: str | os.PathLike) -> int | None:
    steps = list_steps(ckpt_dir)
    return steps[-1] if steps else None


def _apply_retention(ckpt_dir: Path, keep: int, keep_every: int | None):
    steps = list_steps(ckpt_dir)
    if keep <= 0 or len(steps) <= keep:
        return
    protected = set(steps[-keep:])
    if keep_every:
        protected |= {s for s in steps if s % keep_every == 0}
    for s in steps:
        if s not in protected:
            shutil.rmtree(ckpt_dir / f"{_STEP_PREFIX}{s}", ignore_errors=True)


def save_checkpoint(ckpt_dir: str | os.PathLike, step: int, tree, *,
                    metadata: dict | None = None, keep: int = 3,
                    keep_every: int | None = None,
                    process_index: int | None = None,
                    n_processes: int | None = None) -> Path:
    """Write one atomic checkpoint; returns the committed directory."""
    proc = jax.process_index() if process_index is None else process_index
    nproc = jax.process_count() if n_processes is None else n_processes
    d = Path(ckpt_dir)
    d.mkdir(parents=True, exist_ok=True)
    tmp = d / f".tmp-{step}-{os.getpid()}-{time.time_ns()}"
    tmp.mkdir()
    try:
        flat = _flatten(tree)
        np.savez(tmp / f"shard-{proc:05d}.npz", **flat)
        # (multi-host: barrier here so all shards exist before commit)
        if proc == 0:
            meta = {
                "step": int(step),
                "n_processes": int(nproc),
                "paths": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                          for k, v in flat.items()},
                "metadata": metadata or {},
                "time": time.time(),
            }
            (tmp / "meta.json").write_text(json.dumps(meta, indent=1))
            final = d / f"{_STEP_PREFIX}{step}"
            if final.exists():            # re-save of same step: replace
                shutil.rmtree(final)
            os.rename(tmp, final)         # the atomic commit point
            _apply_retention(d, keep, keep_every)
            return final
        return tmp
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise


def restore_checkpoint(ckpt_dir: str | os.PathLike, like, *, step: int | None = None,
                       shardings=None, process_index: int | None = None):
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs).  ``shardings``: optional matching pytree of
    NamedShardings — restored leaves are device_put against them (the
    elastic-reshard path).  Returns (tree, meta)."""
    proc = jax.process_index() if process_index is None else process_index
    d = Path(ckpt_dir)
    if step is None:
        step = latest_step(d)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {d}")
    cdir = d / f"{_STEP_PREFIX}{step}"
    meta = json.loads((cdir / "meta.json").read_text())
    with np.load(cdir / f"shard-{proc:05d}.npz") as z:
        flat = {k: z[k] for k in z.files}

    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    missing = [p for p, _ in paths if _path_str(p) not in flat]
    if missing:
        raise ValueError(f"checkpoint {cdir} missing leaves: "
                         f"{[_path_str(p) for p in missing][:5]}...")
    leaves = []
    for p, leaf in paths:
        k = _path_str(p)
        arr = flat[k]
        want_shape = tuple(leaf.shape)
        if tuple(arr.shape) != want_shape:
            raise ValueError(f"{k}: checkpoint shape {arr.shape} != {want_shape}")
        if arr.dtype.kind == "V":          # npz keeps bfloat16 as raw bytes
            arr = arr.view(leaf.dtype)
        leaves.append(arr.astype(leaf.dtype) if hasattr(leaf, "dtype") else arr)
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    if shardings is not None:
        tree = jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)
    return tree, meta


class AsyncCheckpointer:
    """At-most-one-in-flight background checkpoint writer.

    ``save()`` snapshots the tree to host memory synchronously (cheap: a
    device->host copy) and enqueues the disk write, so the train loop only
    ever blocks on I/O if a previous save is still running (back-pressure,
    never unbounded memory).  ``wait()`` drains; always call it before
    process exit (the trainer does).
    """

    def __init__(self, ckpt_dir: str | os.PathLike, *, keep: int = 3,
                 keep_every: int | None = None):
        self.ckpt_dir = Path(ckpt_dir)
        self.keep = keep
        self.keep_every = keep_every
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="ckpt")
        self._inflight: Future | None = None
        self._lock = threading.Lock()
        self.saved_steps: list[int] = []

    def save(self, step: int, tree, *, metadata: dict | None = None) -> None:
        host_tree = jax.tree.map(lambda x: np.array(x, copy=True),
                                 tree)   # true snapshot, never a view
        with self._lock:
            if self._inflight is not None:
                self._inflight.result()              # back-pressure
            self._inflight = self._pool.submit(
                save_checkpoint, self.ckpt_dir, step, host_tree,
                metadata=metadata, keep=self.keep, keep_every=self.keep_every)
            self.saved_steps.append(int(step))

    def wait(self) -> None:
        with self._lock:
            if self._inflight is not None:
                self._inflight.result()
                self._inflight = None

    def close(self) -> None:
        self.wait()
        self._pool.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
