"""The trace reduction: device busy time as the union of op intervals,
idle gaps and what the host was doing in them, per-kernel calls and
time; on hand-made traces and on a window recorded on a chip."""
import numpy as np
import pytest

import chipbench_testkit
from chipbench import trace

RECORDED = chipbench_testkit.DATA / "mamba_window.json.gz"


def test_short_op_names():
    hlo = ("%flash_attention.2 = bf16[32,16,1024,128]{3,2,1,0:T(8,128)(2,1)} custom-call("
           "bf16[32,16,1024,128]{3,2,1,0} %x), custom_call_target=\"tpu_custom_call\"")
    s = trace.short_op(hlo)
    assert s == "flash_attention.2 bf16[32,16,1024,128] custom-call"
    assert trace.op_base(s) == "flash_attention" and trace.is_kernel(s, "flash_attention")
    tup = ("%ssd_scan.2 = (bf16[64,32,512,64]{3,2,1,0:T(8,128)(2,1)}, f32[64,32,64,128]{3,2,1,0}) "
           "custom-call(f32[32]{0} %a)")
    s = trace.short_op(tup)
    assert s == "ssd_scan.2 (bf16[64,32,512,64], f32[64,32,64,128]) custom-call"
    assert trace.is_kernel(s, "ssd_scan") and not trace.is_kernel(s, "ssd")
    fusion = "%fusion.6 = bf16[32,1024,11008]{2,1,0} fusion(bf16[32,1024,2048]{2,1,0} %b), kind=kOutput"
    assert not trace.is_kernel(trace.short_op(fusion), "fusion")


def test_intervals():
    cover = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 12)])
    assert cover == [(0, 3), (5, 8), (10, 12)]
    assert trace.gaps(cover, -1, 13) == [(-1, 0), (3, 5), (8, 10), (12, 13)]
    assert trace.overlap(cover, 2, 11) == 1 + 3 + 1
    assert trace.clip(cover, 1, 11) == [(1, 3), (5, 8), (10, 11)]


def _hand_trace():
    # window [100, 200): ops on device 0 cover [100,120) [110,130) [150,160);
    # an op on device 1 and ops outside the window do not count
    return {
        "device_ops": [[0, "a.1 f32[4] fusion", 100, 20], [0, "k.3 f32[8] custom-call", 110, 20],
                       [0, "k.4 f32[8] custom-call", 150, 10], [1, "b.1 f32[2] fusion", 100, 100],
                       [0, "c.1 f32[2] fusion", 10, 20]],
        "host": [["main/7", "chipbench.window", 100, 100], ["main/7", "Execute", 125, 10],
                 ["pool/9", "Allocate", 170, 40]],
    }


def test_reduce_hand_trace():
    red = trace.reduce(_hand_trace(), (100, 200), [0], ["k"])
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)              # [100,130) + [150,160)
    assert red["kernels"]["k"] == {"calls": 2, "seconds": pytest.approx(30e-9)}
    idle = dict(red["idle_gaps"])
    assert idle["main: Execute"] == pytest.approx(5e-9)        # [130,135) of [130,150)
    assert idle["pool: Allocate"] == pytest.approx(30e-9)      # [170,200)
    assert idle["no host event"] == pytest.approx(15e-9 + 10e-9)   # [135,150) [160,170)
    assert dict(red["device_ops"])["a f32[4]"] == pytest.approx(20e-9)


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(RECORDED))


def test_recorded_window_is_small():
    assert RECORDED.stat().st_size < 1_100_000


def test_recorded_busy_is_the_union(recorded):
    """Busy time from the reduction equals a brute-force union on a
    microsecond grid, and lies inside the window."""
    lo, hi = trace.mark(recorded, "chipbench.window")
    red = trace.reduce(recorded, (lo, hi), [0], ["ssd_scan"])
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for d, _, s, du in recorded["device_ops"]:
        if d == 0:
            a, b = max(s, lo), min(s + du, hi)
            if b > a:
                grid[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = True
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    idle = sum(t for n, t in red["idle_gaps"] if n == "no host event")
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9


def test_recorded_kernel_calls(recorded):
    """One wave of the mamba cell, prefill and two decode steps: ssd_scan
    runs once per layer per group prefill, 2 x 48 calls."""
    window = trace.mark(recorded, "chipbench.window")
    red = trace.reduce(recorded, window, [0], ["ssd_scan", "flash_attention"])
    assert red["kernels"]["ssd_scan"]["calls"] == 96
    assert red["kernels"]["ssd_scan"]["seconds"] > 0
    assert red["kernels"]["flash_attention"] == {"calls": 0, "seconds": 0.0}
    tops = [t for _, t in red["device_ops"]]
    assert tops == sorted(tops, reverse=True) and len(tops) == 10
