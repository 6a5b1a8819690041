"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (required: smoke tests see 1 device; only
dryrun.py forces 512 host devices)."""
from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    JAX 0.9 makes ``Explicit`` the default axis type, under which gathers
    such as ``jnp.take`` on a sharded operand refuse to pick an output
    sharding.  The model code relies on sharding propagation (and
    ``sharding_ctx`` constraints), which is what ``Auto`` axes give."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, tp: int | None = None,
                         rep: int | None = None):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: "data" carries batch (and FSDP param sharding), "model" carries
    tensor/expert parallelism, "pod" is the slow inter-pod (DCN) data axis.

    ``tp`` reshapes the pod's 256 chips to (256//tp, tp) — the planner's
    space/time knob (§Perf variants).  The canonical dry-run mesh is the
    default tp=16."""
    tp = 16 if tp is None else int(tp)
    assert 256 % tp == 0 and tp >= 1, f"bad tp={tp}"
    if rep:
        # three-axis pod: "data" keeps expert parallelism at width
        # 256//(tp*rep); "rep" is extra pure-DP; "model" is within-expert TP
        assert 256 % (tp * rep) == 0
        shape = (256 // (tp * rep), rep, tp)
        axes = ("data", "rep", "model")
        if multi_pod:
            shape = (2, *shape)
            axes = ("pod", *axes)
        return auto_mesh(shape, axes)
    shape = (2, 256 // tp, tp) if multi_pod else (256 // tp, tp)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Axes over which the batch is sharded (everything but "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def model_axis(mesh) -> str:
    return "model"


def mesh_device_count(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def stage_device_slices(mesh_or_devices, stg, sel) -> dict:
    """Partition a mesh's device set into per-stage replica slices.

    The spatial alternative to the folded (data, model) layout: each stage
    of the plan gets tp-sized device tuples, one per replica, in topological
    order (runtime.pipeline pins stage params to these).  Accepts a jax
    Mesh or any device sequence.  ``stage_submeshes`` lifts the same
    partition to per-replica jax sub-meshes for tp-sharded stage params.
    """
    from ..runtime.pipeline.placement import place
    devs = _pool(mesh_or_devices)
    pl = place(stg, sel, devs)
    out: dict = {}
    for sl in pl.slices.values():
        out.setdefault(sl.stage, []).append((sl.replica, sl.devices))
    return {k: [d for _, d in sorted(v)] for k, v in out.items()}


def stage_submeshes(mesh_or_devices, stg, sel) -> dict:
    """Per-stage, per-replica ("data", "model") sub-meshes of shape (1, tp).

    The heterogeneous-mesh half of the spatial layout: each tp>1 replica
    slice becomes its own 1 x tp mesh so the stage's params shard over the
    slice (`launch/sharding.stage_param_specs`) instead of living on the
    slice's first device.  Entries are ``None`` where a sub-mesh cannot be
    built honestly: tp == 1 (nothing to shard) or a slice folded onto
    repeated devices by oversubscription (a mesh with duplicate devices is
    invalid — the executor falls back to single-device placement there).
    """
    from ..runtime.pipeline.placement import place
    devs = _pool(mesh_or_devices)
    pl = place(stg, sel, devs)
    out: dict = {}
    for sl in pl.slices.values():
        out.setdefault(sl.stage, []).append(
            (sl.replica, submesh_of(sl.resolve(devs))))
    return {k: [m for _, m in sorted(v, key=lambda t: t[0])]
            for k, v in out.items()}


def submesh_of(devices):
    """A (1, tp) ("data", "model") Mesh over one replica's device tuple, or
    None when no honest sub-mesh exists: tp == 1 (nothing to shard),
    repeated devices (a slice folded by oversubscription), or abstract
    integer handles (the interpreter's device model)."""
    import numpy as np
    if len(devices) < 2 or len(set(devices)) != len(devices):
        return None
    if not all(hasattr(d, "platform") for d in devices):
        return None
    return jax.sharding.Mesh(
        np.asarray(devices, dtype=object).reshape(1, len(devices)),
        ("data", "model"))


def _pool(mesh_or_devices) -> list:
    return (list(mesh_or_devices.devices.flat)
            if hasattr(mesh_or_devices, "devices") else list(mesh_or_devices))
