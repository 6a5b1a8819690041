"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

A run makes the weights on the device from the seed, builds the cell's
client entry over the program's serving path, warms only the shape
classes its traffic holds, drives the closed loop for ``--seconds``,
checks what was served against the plain reference, and prints one JSON
line last: the cell's end-to-end metrics (``--trace 0``) or its
per-layer metrics with the device's busy time and a breakdown
(``--trace 1``).  Without a TPU it exits with code 3 and prints no
result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass

from . import check, counts, registry, stats, traffic, weights
from . import trace as trace_mod
from .peaks import peaks_of

NO_CHIP = 3
WORK_DIR = registry.REPO / ".chipbench"          # run-time files, in the checkout
TRACE_DIR = WORK_DIR / "trace"
CACHE_DIR = WORK_DIR / "jax_cache"
COMPILE_EVENTS = ("backend_compile_duration", "cache_retrieval_time")


class _Phases:
    """Prints how long each set-up phase took and the devices' memory peak
    after it: the largest, and each chip's where there are several."""

    def __init__(self, devices):
        self.devices, self.t = devices, time.perf_counter()

    def __call__(self, name: str) -> None:
        now = time.perf_counter()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        each = ""
        if len(peaks) > 1:
            each = " (" + ", ".join(f"{d.id}: {p / 1e9:.3f}"
                                    for d, p in zip(self.devices, peaks)) + ")"
        _say(f"set-up {name}: {now - self.t:.3f} s, memory peak {max(peaks) / 1e9:.3f} GB{each}")
        self.t = now


@dataclass
class Cell:
    """A cell made ready to serve: weights drawn, traffic generated, the
    entry built over the program and every shape class warmed."""
    model: dict
    mix: dict
    ref: object                 # the configuration's reference module
    params: dict
    waves: list
    session: object
    max_prompt: int
    max_out: int

    @classmethod
    def build(cls, config: str, mix_name: str, seed: int, devices) -> "Cell":
        spec = registry.config(config)
        m = spec["model"]
        mix = registry.traffic(mix_name)
        ref = registry.reference(m["kind"])
        phase = _Phases(devices)
        params = weights.make(ref.layout(m), seed, devices)
        phase("weights")
        waves = registry.generator(mix["kind"]).generate(mix, seed, m["vocab"], traffic)
        max_prompt = max(traffic.length_deck(mix["prompt"], mix["max_batch"]))
        max_out = max(traffic.length_deck(mix["output"], mix["max_batch"]))
        phase("traffic")
        sess = registry.entry(mix["entry"]).Session(spec, mix, params, devices,
                                                    max_prompt + max_out)
        phase("entry")
        sess.warm(waves)
        phase("warm-up")
        return cls(m, mix, ref, params, waves, sess, max_prompt, max_out)

    def check_inputs(self, records, seed: int):
        """The reference's inputs for a seeded sample of the requests the
        records finished."""
        prompts = [r.prompt for wave in self.waves[:len(records)] for r in wave]
        dones = [d for rec in records for d in rec.requests]
        served = [check.Served(prompt=p, tokens=d.tokens) for p, d in zip(prompts, dones)
                  if len(d.tokens) == d.max_new]
        sample = check.pick(served, self.mix["check_requests"], seed)
        return sample, check.batch(sample, self.max_prompt + self.max_out, self.max_out)


@dataclass
class Context:
    """What a per-layer metric's reader may read."""
    model: dict                 # the configuration's ``model`` section
    mix: dict
    peaks: dict
    chips: int
    window_s: float             # the traced window: every wave, start to end
    records: list               # one entry WaveRecord per wave
    trace: dict                 # `trace.reduce` of the traced window
    stage_count: int
    counts = counts


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program however quickly it compiled, so that a cell's
    second run finds all of them.  Set before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    bench = registry.benchmark()
    cell = find_cell(bench, args.workload)
    use_checkout_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0]}", file=sys.stderr)
        return NO_CHIP
    if len(devices) < cell["chips"]:
        print(f"{cell['chips']} chips asked for, {len(devices)} present", file=sys.stderr)
        return NO_CHIP
    out = run(bench, cell, args.seed, args.seconds, bool(args.trace),
              devices[:cell["chips"]], t_start)
    print(json.dumps(out))
    return 0


def _say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run(bench: dict, cell: dict, seed: int, seconds: float, traced: bool,
        devices, t_start: float) -> dict:
    """The run itself, on ``devices`` whatever they are (the caller has
    checked them); returns the result line as a dict."""
    import jax
    c = Cell.build(cell["config"], cell["traffic"], seed, devices)
    sess, waves = c.session, c.waves

    readers = {}
    if traced:
        for mm in bench["per_layer"]:
            if cell["name"] in mm.get("workloads", [cell["name"]]):
                readers[mm["name"]] = (registry.metric(mm["name"]), mm["unit"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0           # host events of the runtime only
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    compiles = []
    in_window = [False]

    def on_event(event, duration, **_):
        if in_window[0] and any(k in event for k in COMPILE_EVENTS):
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)

    records = []
    in_window[0] = True
    w0 = time.perf_counter()
    setup_s = w0 - t_start
    w1 = w0 + seconds
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while time.perf_counter() < w1:
            if len(records) == len(waves):
                raise RuntimeError(f"the mix's {len(waves)} waves ran out inside the window")
            with jax.profiler.TraceAnnotation("chipbench.wave"):
                records.append(sess.serve(waves[len(records)]))
    t_end = time.perf_counter()
    in_window[0] = False
    jax.monitoring.unregister_event_duration_listener(on_event)
    if traced:
        jax.profiler.stop_trace()

    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    stage_count, late = sess.stage_count, sess.late_compiles
    sess.close()
    del sess
    c.session = None
    gc.collect()

    dones = [d for rec in records for d in rec.requests]
    failed = sum(len(d.tokens) != d.max_new or len(d.times) != len(d.tokens) for d in dones)
    _say(f"run: {len(records)} waves, {len(dones)} requests, {failed} failed, "
         f"{stage_count} stages, setup {setup_s:.3f} s, window {seconds:g} s, "
         f"last wave returned {t_end - w0:.3f} s after the window opened; "
         f"compiles in the window: {len(compiles)} (program's late compiles {late})")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": max(mem)}
    breakdown = None
    if traced:
        tr = trace_mod.load_xplane(str(TRACE_DIR))
        window = trace_mod.mark(tr, "chipbench.window")
        kernels = sorted({r.KERNEL for r, _ in readers.values() if hasattr(r, "KERNEL")})
        red = trace_mod.reduce(tr, window, [d.id for d in devices], kernels)
        ctx = Context(model=c.model, mix=c.mix, peaks=peaks_of(devices[0].device_kind),
                      chips=len(devices), window_s=t_end - w0, records=records,
                      trace=red, stage_count=stage_count)
        metrics = {}
        for name, (reader, unit) in readers.items():
            value = reader.read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        b, bucket, steps = records[0].groups[0]
        step_bytes = counts.decode_step_bytes(c.model, b, bucket + steps // 2)
        _say(f"a decode step of a group of {b} at {bucket + steps // 2} positions moves "
             f"at least {step_bytes:.4g} bytes: {1e3 * step_bytes / ctx.peaks['hbm_bytes_per_s']:.3f}"
             f" ms at the peak bandwidth")
        _say("kernels in the trace: " + ", ".join(
            f"{k} {v['calls']} calls {v['seconds']:.6f} s" for k, v in red["kernels"].items()))
    else:
        metrics = end_to_end(bench, cell, records, w0, w1, setup_s, max(mem))

    # the check, once the program's state is freed and the peak is read
    lim = registry.limits(cell["name"])
    t_ref = time.perf_counter()
    sample, inputs = c.check_inputs(records, seed)
    numbers = check.served_gaps(c.ref, c.params, c.model, inputs)
    _say(f"reference over {len(sample)} requests, {sum(len(s.tokens) for s in sample)} "
         f"served tokens: {time.perf_counter() - t_ref:.3f} s; " + ", ".join(
             f"{k} {v!r}" for k, v in numbers.items()))
    correct, checks = check.verdict(lim, numbers, failed)
    for name, chk in checks.items():
        _say(f"check {name} {chk['value']!r} limit {chk['limit']!r}")
    out = {"correct": correct, "attempted": len(dones), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def end_to_end(bench, cell, records, w0, w1, setup_s, peak_bytes) -> dict:
    """The cell's end-to-end metrics over everything inside [w0, w1]."""
    n_tokens, token_gaps, ttfts = 0, [], []
    for rec in records:
        for d in rec.requests:
            ts = d.times
            n_tokens += sum(w0 <= t <= w1 for t in ts)
            if ts and w0 <= ts[0] <= w1:
                ttfts.append(ts[0] - d.handover)
            token_gaps.extend(b - a for a, b in zip(ts, ts[1:]) if a >= w0 and b <= w1)
    _say(f"samples: {n_tokens} tokens, {len(ttfts)} first tokens, "
         f"{len(token_gaps)} token gaps inside the window")
    values = {
        "output_tokens_per_s": n_tokens / (w1 - w0),
        "itl_p95_ms": stats.percentile(token_gaps, 95) * 1e3,
        "ttft_p90_ms": stats.percentile(ttfts, 90) * 1e3,
        "peak_hbm_gb": peak_bytes / 1e9,
        "setup_s": setup_s,
    }
    return {mm["name"]: {"value": values[mm["name"]], "unit": mm["unit"]}
            for mm in bench["end_to_end"]
            if cell["name"] in mm.get("workloads", [cell["name"]])}
