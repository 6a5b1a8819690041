"""Run one benchmark cell once on the chip: see `chipbench.cli`.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>
"""
import time

T_START = time.perf_counter()       # set-up is timed from here

import sys                          # noqa: E402
from pathlib import Path            # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

if __name__ == "__main__":
    from chipbench import cli
    sys.exit(cli.main(t_start=T_START))
