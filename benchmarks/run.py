"""Benchmark harness: one module per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table2 roofline
    PYTHONPATH=src python -m benchmarks.run pipeline --json-dir artifacts
    PYTHONPATH=src python -m benchmarks.run pipeline --smoke --json-dir a

``--json-dir DIR`` writes each bench's rows to ``DIR/BENCH_<name>.json``
(benches whose runners return rows / accept ``json_path``).  CI uploads
the directory as an artifact so the perf trajectory accumulates run over
run instead of living only in job logs.  ``--smoke`` forwards to benches
whose runners accept it (fast PR-CI subsets; others run in full).
"""
from __future__ import annotations

import inspect
import json
import os
import sys
import time

BENCHES = [
    ("table1", "bench_table1", "run"),
    ("table2", "bench_table2", "run"),
    ("fig4", "bench_fig4", "run"),
    ("fig8", "bench_fig8", "run"),
    ("streamit", "bench_streamit", "run_bench"),
    ("solver_speed", "bench_solver_speed", "run"),
    ("compress", "bench_compress", "run"),
    ("planner", "bench_planner", "run"),
    ("roofline", "bench_roofline", "run"),
    ("pipeline", "bench_pipeline", "run"),
    ("serve", "bench_serve", "run"),
]


def _invoke(fn, name: str, json_dir: str | None, smoke: bool = False):
    """Run one bench; route rows to BENCH_<name>.json when a dir is set."""
    kwargs = {"verbose": True}
    params = inspect.signature(fn).parameters
    if smoke and "smoke" in params:
        kwargs["smoke"] = True
    json_path = (os.path.join(json_dir, f"BENCH_{name}.json")
                 if json_dir else None)
    if json_path and "json_path" in params:
        kwargs["json_path"] = json_path
        json_path = None                   # the bench writes it itself
    out = fn(**kwargs)
    if json_path and out is not None:
        try:
            # serialise fully before touching the file so a mid-stream
            # TypeError cannot leave a truncated artifact for CI to upload
            payload = json.dumps(out, indent=2, default=str)
        except TypeError as e:
            print(f"skipping {json_path}: return value not "
                  f"JSON-serialisable ({e})")
            return
        with open(json_path, "w") as f:
            f.write(payload)
        print(f"wrote {json_path}")


def main(argv=None) -> None:
    from repro import compile_cache
    compile_cache.enable()
    argv = sys.argv[1:] if argv is None else argv
    json_dir = None
    smoke = "--smoke" in argv
    if smoke:
        argv = [a for a in argv if a != "--smoke"]
    if "--json-dir" in argv:
        i = argv.index("--json-dir")
        if i + 1 >= len(argv):
            raise SystemExit("usage: benchmarks.run [names...] --json-dir DIR")
        json_dir = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
        os.makedirs(json_dir, exist_ok=True)
    wanted = set(argv) if argv else None
    failures = []
    for name, mod_name, fn_name in BENCHES:
        if wanted is not None and name not in wanted:
            continue
        print()
        print("#" * 72)
        print(f"## bench: {name}")
        print("#" * 72)
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=[fn_name])
            _invoke(getattr(mod, fn_name), name, json_dir, smoke)
            print(f"[{name}: {time.perf_counter()-t0:.1f}s]")
        except Exception as e:
            failures.append((name, repr(e)))
            import traceback
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks completed")


if __name__ == "__main__":
    main()
