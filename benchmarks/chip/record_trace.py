"""Record the small chip trace the benchmark's tests reduce.

    python3 benchmarks/chip/record_trace.py --workload <cell> --out <file.json.gz>

Builds the cell as a run does, then traces one wave's prefill and two
decode steps (the server's admission pause after two steps) inside the
harness's own window span, brings the trace to the benchmark's plain
form, keeps the window only, and writes it gzipped.  Prints what the
reduction reads from it.
"""
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    import argparse
    from chipbench import cli, registry
    from chipbench import trace as trace_mod
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = cli.find_cell(registry.benchmark(), args.workload)
    cli.use_checkout_cache()
    import jax
    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu":
        return cli.NO_CHIP
    c = cli.Cell.build(cell["config"], cell["traffic"], 1, devices)
    wave = c.waves[0]
    shutil.rmtree(cli.TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(cli.TRACE_DIR), profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        c.session.pipe.serve([r.prompt.tolist() for r in wave], [r.max_new for r in wave],
                             eos_id=-1, group_size=c.mix["max_batch"], pause_after_tokens=2)
    jax.profiler.stop_trace()
    tr = trace_mod.load_xplane(str(cli.TRACE_DIR))
    lo, hi = trace_mod.mark(tr, "chipbench.window")
    kept = {"device_ops": [e for e in tr["device_ops"] if e[2] + e[3] > lo and e[2] < hi],
            "host": [e for e in tr["host"] if e[2] + e[3] > lo and e[2] < hi]}
    trace_mod.save(kept, args.out)
    red = trace_mod.reduce(kept, (lo, hi), [d.id for d in devices],
                           ["ssd_scan", "flash_attention", "decode_attention"])
    print(json.dumps({"size": Path(args.out).stat().st_size, "device_ops": len(kept["device_ops"]),
                      "host": len(kept["host"]), "reduce": red}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
