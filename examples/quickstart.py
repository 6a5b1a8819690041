"""Quickstart: the paper's space/time trade-off, from JPEG to TPU pods.

Part 1 reproduces the paper's own experiment: the JPEG encoder STG with its
Table-1 implementation library, solved by both the ILP (Eq. 3/4) and the
heuristic (bottleneck budgeting + node combining) at the published inverse
throughput targets — the heuristic uses substantially less area (Table 2).

Part 2 runs the *same* trade-off machinery on a modern workload: qwen2.5-3b
training as a streaming task graph over TPU v5e chips, in both of the
paper's modes (area budget -> throughput; throughput target -> chips), and
shows elastic re-planning when the chip budget changes.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

from repro.configs import SHAPES, get_config
from repro.core import heuristic, ilp, planner
from repro.core.fork_join import JPEG_CALIBRATED
from repro.graphs import jpeg


def part1_jpeg():
    print("=" * 72)
    print("Part 1 — paper reproduction: JPEG encoder, ILP vs heuristic")
    print("=" * 72)
    stg = jpeg.build_stg()
    print(f"{'v_tgt':>6s} {'ILP area':>10s} {'heur area':>10s} {'saving':>8s}")
    for v_tgt in (1, 2, 4, 8):
        r_ilp = ilp.min_area(stg, v_tgt, JPEG_CALIBRATED)
        r_heu = heuristic.min_area(stg, v_tgt, JPEG_CALIBRATED)
        save = 1 - r_heu.total_area / r_ilp.total_area
        print(f"{v_tgt:6d} {r_ilp.total_area:10.0f} {r_heu.total_area:10.0f} "
              f"{save:8.0%}")
    print("\n(the ILP cannot express node combining — paper §II.B.1)")


def part2_lm():
    print()
    print("=" * 72)
    print("Part 2 — the same trade-off on a TPU pod: qwen2.5-3b train_4k")
    print("=" * 72)
    cfg = get_config("qwen2.5-3b")
    shape = SHAPES["train_4k"]

    print("\nMode 1: one pod (256 chips) -> maximise throughput")
    p = planner.plan(cfg, shape, chips=256)
    print(p.summary())
    ex = planner.to_execution(p, cfg=cfg, chips=256)
    print(f"  -> GSPMD projection: mesh {ex.mesh_shape} "
          f"(dp={ex.dp}, tp={ex.tp}), fsdp={ex.fsdp}")

    print("\nMode 2: hit 1M train tokens/s -> minimise chips (ILP vs heuristic)")
    for eng in ("ilp", "heuristic"):
        q = planner.plan(cfg, shape, tokens_per_s=1e6, engine=eng)
        print(f"  {eng:9s}: {q.total_chips:6.1f} chips "
              f"({q.impl_chips:.0f} impl + {q.overhead_chips:.1f} routing), "
              f"achieves {q.tokens_per_s:,.0f} tok/s")

    print("\nElastic: the pod shrinks to 128 chips -> re-plan")
    new, diff = planner.replan(cfg, shape, p, new_chips=128)
    print(f"  {diff['chips'][0]:.0f} -> {diff['chips'][1]:.0f} chips, "
          f"throughput x{diff['throughput_ratio']:.2f}, "
          f"{len(diff['stages_changed'])} stages re-laid-out")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    part1_jpeg()
    part2_lm()
