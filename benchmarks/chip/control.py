"""Readings that set a cell's limits, many seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11,12,13

For each seed the cell is built as a run builds it and its first wave is
served: every answer length of the mix, the longest included.  On the
run's seeded sample of that wave it reads the program's numbers against
the float32 reference (the lower readings) and, on the first three
seeds, the control's: the reference computed in int8 and in fp8, put in
the program's place, its first tokens read against the float32 logits
the same way (the upper readings).  Each set of numbers goes through the
run's own verdict against the cell's limits.  One JSON line per seed.
Runs on the chip only, like ``run.py``; the benchmark's own runs never
run this.
"""
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

CONTROLS = ("int8", "fp8")
CONTROL_SEEDS = 3


def main(argv=None) -> int:
    import argparse
    from chipbench import check, cli, registry
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = cli.find_cell(registry.benchmark(), args.workload)
    lim = registry.limits(cell["name"])
    cli.use_checkout_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX has {devices}", file=sys.stderr)
        return cli.NO_CHIP
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        c = cli.Cell.build(cell["config"], cell["traffic"], seed, devices[:cell["chips"]])
        rec = c.session.serve(c.waves[0])
        c.session.close()
        c.session = None
        gc.collect()
        _, inputs = c.check_inputs([rec], seed)
        failed = sum(len(d.tokens) != d.max_new for d in rec.requests)
        readings = {"program": check.served_gaps(c.ref, c.params, c.model, inputs)}
        if k < CONTROL_SEEDS:
            for q in CONTROLS:
                readings[q] = check.control_gaps(c.ref, c.params, c.model, inputs, q)
        row = {"seed": seed}
        for who, numbers in readings.items():
            correct, _ = check.verdict(lim, numbers, failed)
            row[who] = dict(numbers, correct=correct)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del c, rec, inputs
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
