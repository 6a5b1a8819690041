"""Gradient-compression wire bytes: int8 ring vs f32 all-reduce.

Lowers both sync schemes for a 16-way data axis on simulated devices and
prices the collective traffic with the same HLO parser the roofline uses.
Expected: the quantized ring moves ~4x fewer bytes than an f32 ring
all-reduce (int8 payload both directions, ppermute chains).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import auto_mesh
    from repro.optim.compress import compressed_mean
    from repro.analysis import hlo as H

    mesh = auto_mesh((16,), ("data",))
    N = 1 << 22          # 4M f32 grads per device (16 MB)

    def ring(x):
        return compressed_mean(x[0], "data", 16)[None]

    def psum_mean(x):
        return (jax.lax.psum(x[0], "data") / 16)[None]

    import numpy as np
    xs = jax.ShapeDtypeStruct((16, N), jnp.float32)
    out = {}
    for name, fn in (("int8_ring", ring), ("f32_allreduce", psum_mean)):
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
        txt = f.lower(xs).compile().as_text()
        coll = H.collect(txt, 16)
        out[name] = coll.total()
        print(f"{name:14s} wire={coll.total()/1e6:10.1f} MB  "
              f"{ {k: round(v/1e6,1) for k,v in coll.wire_bytes.items()} }")
    print(f"ratio f32/int8 = {out['f32_allreduce']/out['int8_ring']:.2f}x")
""")


def run(verbose=True):
    # the child lowers for 16 virtual CPU devices; pinned to the CPU
    # backend so it never reaches for an accelerator this process may hold
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if verbose:
        print("# int8 ring reduce-scatter+all-gather vs f32 all-reduce "
              "(16-way, 16MB grads)")
        print(r.stdout.strip() or r.stderr[-1500:])
    assert r.returncode == 0, r.stderr[-1500:]
    return r.stdout


if __name__ == "__main__":
    run()
