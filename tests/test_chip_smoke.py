"""``chip_smoke.py`` rehearsed without a chip.

On a host without a TPU the script must refuse to run and say so in its
last line.  Its phases — plan, place, pipelined serve, and the logits
comparisons against the single-device server and ``impl="ref"`` — run
here on CPU devices with the Pallas kernels in interpret mode, at a tiny
width, so a fault in the script's own logic shows before any chip time
is spent.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax

from repro.configs import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke_module():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_cleanly_without_a_tpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 1, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": {"platform": "cpu",
                                            "kind": "cpu", "count": 1}}
    assert "no TPU" in r.stderr


def test_chip_smoke_phases_rehearse_on_one_cpu_device(capsys):
    smoke = _smoke_module()
    smoke.run(get_config("tiny"), jax.devices()[:1], seed=0, chips=1,
              impl="interpret", prompt_range=(8, 24), on_tpu=False)
    out = capsys.readouterr().out
    assert "compile_stats.late=0" in out
    assert "kernel path vs ref logits" in out


_FOUR = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "src")
    import importlib.util
    import jax
    from repro.configs import get_config
    spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert len(jax.devices()) == 4
    smoke.run(get_config("tiny"), jax.devices(), seed=0, chips=4,
              impl="interpret", prompt_range=(8, 24), on_tpu=False)
    print("FOUR_OK")
""")


def test_chip_smoke_four_device_phase_rehearses_on_virtual_cpus():
    """``--chips 4``'s phase: the one-chip plan spread over four devices,
    every stage where its placement slice says, the logits equal to the
    single-device server's, and no comparison against ref."""
    r = subprocess.run([sys.executable, "-c", _FOUR], cwd=ROOT,
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-2500:])
    assert "FOUR_OK" in r.stdout
    assert "stages per device" in r.stdout
    assert "kernel path vs ref" not in r.stdout
