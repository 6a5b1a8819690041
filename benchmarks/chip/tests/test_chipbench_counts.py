"""The benchmark's FLOP and byte counts against values worked by hand,
and the table of peaks.  A model kind's counts sit in its reference file
and a kernel's in its roofline metric's file, each found by name."""
import pytest

import chipbench_testkit  # noqa: F401
from chipbench import counts, registry
from chipbench.peaks import peaks_of

QWEN = registry.config("qwen2.5-3b")["model"]
MAMBA = registry.config("mamba2-370m")["model"]


def test_qwen_layer_and_request_flops():
    # q (2048x2048), k and v (2048x256 each), o (2048x2048), 3 MLP matrices
    # of 2048x11008: 2 FLOPs a multiply-add
    proj = 2 * 2048 * 2048 + 2 * 2 * 2048 * 256 + 2 * 2048 * 2048
    mlp = 2 * 3 * 2048 * 11008
    assert counts.layer_flops(QWEN, 0) == proj + mlp == 154_140_672
    assert counts.layer_flops(QWEN, 10) - counts.layer_flops(QWEN, 0) == 4 * 16 * 128 * 10
    # prompt of 2, 2 generated: 3 positions fed (contexts 1, 2, 3), 2 heads
    want = 36 * (3 * 154_140_672 + 8192 * (1 + 2 + 3)) + 2 * (2 * 2048 * 151_936)
    assert counts.request_flops(QWEN, 2, 2) == want == 17_893_621_760


def test_mamba_layer_flops():
    # x/z in-projection 1024x4096, B/C/dt 1024x(128+128+32), out 2048x1024,
    # 4-tap conv over 2048 channels, state update + read-out 32x64x128
    want = (2 * 1024 * 4096 + 2 * 1024 * 288 + 2 * 2048 * 1024
            + 2 * 4 * 2048 + 4 * 32 * 64 * 128)
    assert counts.layer_flops(MAMBA, 0) == counts.layer_flops(MAMBA, 999) == want == 14_237_696


def _kernel_call(kernel):
    return registry.metric(f"{kernel}_roofline").call


def test_kernel_calls():
    f, b = _kernel_call("decode_attention")(QWEN, 32, 1100)
    assert f == 4 * 32 * 16 * 128 * 1100 == 288_358_400
    assert b == (2 * 32 * 1100 * 2 * 128 + 2 * 32 * 16 * 128) * 2 == 36_306_944
    f, b = _kernel_call("flash_attention")(QWEN, 32, 1024)
    assert f == 4 * 32 * 16 * 128 * 1024 * 1025 / 2 == 137_573_171_200
    assert b == 32 * 1024 * (2 * 16 + 2 * 2) * 128 * 2 == 301_989_888
    f, b = _kernel_call("ssd_scan")(MAMBA, 64, 512)
    assert f == 4 * 64 * 512 * 32 * 64 * 128 == 34_359_738_368
    assert b == ((2 * 64 * 512 * 32 * 64 + 2 * 64 * 512 * 128) * 2
                 + 64 * 512 * 32 * 4 + 64 * 32 * 64 * 128 * 4) == 356_515_840


def test_decode_step_bytes():
    # qwen, batch 1, 10 live slots: per layer norms (2 x 2048 x 4), weights
    # (2048x2560 + 2048x2048 + 3 x 2048x11008) x 2, biases 2560 x 2, kv
    # read 10 x 2 x 128 x 2 x 2, one slot written 2 x 128 x 2 x 2,
    # activation in and out 2 x 2048 x 2; then the embedding row, the final
    # norm, the head's weights and its logits
    per = (2 * 2048 * 4 + (2048 * 2560 + 2048 * 2048 + 3 * 2048 * 11008) * 2
           + 2560 * 2 + 10 * 2 * 128 * 2 * 2 + 2 * 128 * 2 * 2 + 2 * 2048 * 2)
    want = 36 * per + 2048 * 2 + 2048 * 4 + 2048 * 152064 * 2 + 152064 * 2
    assert counts.decode_step_bytes(QWEN, 1, 10) == want


def test_mamba_decode_step_bytes():
    # batch 1: per layer norm and weights (1024x4096 + 1024x288 + 4x2048 +
    # 2048x1024) x 2, float32 dt_bias, a_log, D (3 x 32) and gate norm 2048,
    # conv history 3 x 2048 x 2 read and written, float32 state 32x64x128
    # read and written, the unused mlp norm, activation in and out
    per = (1024 * 4 + (1024 * 4096 + 1024 * 288 + 4 * 2048 + 2048 * 1024) * 2
           + (3 * 32 + 2048) * 4 + 2 * 3 * 2048 * 2 + 2 * 32 * 64 * 128 * 4
           + 1024 * 4 + 2 * 1024 * 2)
    want = 48 * per + 1024 * 2 + 1024 * 4 + 1024 * 50432 * 2 + 50432 * 2
    assert counts.decode_step_bytes(MAMBA, 1, 999) == want


def test_unknown_kind_has_no_counts():
    with pytest.raises(FileNotFoundError):
        counts.layer_flops(dict(QWEN, kind="no_such_kind"), 0)


def test_least_time_takes_the_larger_bound():
    p = peaks_of("TPU v5 lite")
    assert counts.least_time_s(197e12, 0, p) == pytest.approx(1.0)
    assert counts.least_time_s(1, 819e9, p) == pytest.approx(1.0)


def test_peaks_by_device_kind():
    p = peaks_of("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        peaks_of("TPU v4")
