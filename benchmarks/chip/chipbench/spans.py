"""The program's own spans in a traced run, and what the readers of
``program_span`` metrics compute from them.

While a profiler is active the serving path writes named host spans into
its trace (``serve.prepare``, ``serve.engine.start`` / ``.end`` marks,
``engine.sweep`` / ``.wait`` / ``.retire``, ``head.sample``,
``stage.<program>``, ``compile.<program>``), with counters as span
metadata.  `trace.load_xplane` keeps names and times only, so this module
reads the run's xplane again (`of`, once for all readers of a run) into
a plain form:

    {"window": [lo_ns, hi_ns],
     "spans": [[thread, name, start_ns, dur_ns, {counter: value}], ...],
     "device_ops": [[device, start_ns, dur_ns], ...]}

Program spans are clipped to the harness's ``chipbench.window`` mark.
A program that writes no spans (one from before they existed) gives
None, and every reader then reads nothing.  On first load the device's
idle time in the window is printed to stderr by the innermost program
span open in it — at each instant the open span that started last, so a
worker's stage op inside the engine's wait takes the time — and the part
under no program span as ``untraced``; then each span's count, time and
self time (its time less the time of the spans nested in it on its own
thread).
"""
from __future__ import annotations

import glob
import heapq
import os
import sys
from collections import defaultdict

from . import trace as trace_mod

WINDOW = "chipbench.window"
NAMES = ("serve.prepare", "serve.engine.start", "serve.engine.end",
         "engine.sweep", "engine.wait", "engine.retire", "head.sample",
         "serve.finish")
PREFIXES = ("stage.", "compile.")
UNTRACED = "untraced"


def is_program_span(name: str) -> bool:
    return name in NAMES or name.startswith(PREFIXES)


def load_xplane(trace_dir: str, devices) -> dict:
    """The plain form of the one ``*.xplane.pb`` under ``trace_dir``:
    the window mark, every program span with its counters, and the
    operations of ``devices`` (TPU ids)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"{len(files)} xplane files under {trace_dir}")
    pd = ProfileData.from_file(files[0])
    window, spans, ops = None, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            if dev not in devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([dev, ev.start_ns, ev.duration_ns] for ev in line.events)
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                thread = f"{line.name}/{i}"
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = [ev.start_ns, ev.start_ns + ev.duration_ns]
                    elif is_program_span(ev.name):
                        spans.append([thread, ev.name, ev.start_ns, ev.duration_ns,
                                      dict(ev.stats)])
    return {"window": window, "spans": spans, "device_ops": ops}


def of(ctx) -> dict | None:
    """The traced run's plain form, or None when the program wrote no span
    in the window; loaded by the run's first reader and kept on its
    reading context for the others."""
    if not hasattr(ctx, "program_spans"):
        from . import cli
        tr = None
        if glob.glob(os.path.join(str(cli.TRACE_DIR), "**", "*.xplane.pb"), recursive=True):
            tr = load_xplane(str(cli.TRACE_DIR), set(range(ctx.chips)))
            tr = tr if tr["window"] is not None and clipped(tr) else None
        if tr is not None:
            report(tr, ctx.chips)
        ctx.program_spans = tr
    return ctx.program_spans


def clipped(tr: dict) -> list:
    """``(thread, name, start, end, counters)`` of each program span that
    overlaps the window, its times clipped to it, in start order (an
    enclosing span before the spans it holds); worked out once."""
    if "_clipped" not in tr:
        lo, hi = tr["window"]
        out = [(t, n, max(s, lo), min(s + d, hi), st) for t, n, s, d, st in tr["spans"]
               if s + d >= lo and s <= hi]
        tr["_clipped"] = sorted(out, key=lambda sp: (sp[2], -sp[3]))
    return tr["_clipped"]


def _named(tr: dict, name: str) -> list:
    return [sp for sp in clipped(tr) if sp[1] == name]


def _mean_ms(spans) -> float | None:
    return 1e-6 * sum(e - s for _, _, s, e, _ in spans) / len(spans) if spans else None


def prepare_ms(tr: dict) -> float | None:
    """Mean ``serve.prepare`` span, ms: one per serve (a wave)."""
    return _mean_ms(_named(tr, "serve.prepare"))


def sample_ms(tr: dict) -> float | None:
    """Mean ``head.sample`` span, ms: one per head retirement."""
    return _mean_ms(_named(tr, "head.sample"))


def engine_ns(tr: dict) -> float:
    """Σ from each ``serve.engine.start`` mark to the ``.end`` mark after
    it: the time the engines ran."""
    ends = sorted(s for _, _, s, _, _ in _named(tr, "serve.engine.end"))
    total = 0.0
    for _, _, s, _, _ in _named(tr, "serve.engine.start"):
        i = next((i for i, e in enumerate(ends) if e >= s), None)
        if i is not None:
            total += ends.pop(i) - s
    return total


def wait_share(tr: dict) -> float | None:
    """Σ ``engine.wait`` over the time the engines ran, %."""
    run = engine_ns(tr)
    waits = _named(tr, "engine.wait")
    if run <= 0 or not waits:
        return None
    return 100.0 * sum(e - s for _, _, s, e, _ in waits) / run


def self_times(tr: dict) -> dict[str, list]:
    """name -> [count, time ns, self time ns]: a span's self time is its
    time less that of the spans nested directly in it on its thread."""
    out: dict[str, list] = defaultdict(lambda: [0, 0.0, 0.0])
    by_thread = defaultdict(list)
    for sp in clipped(tr):
        by_thread[sp[0]].append(sp)
    for spans in by_thread.values():
        stack: list = []                     # open spans: (end, name)
        for _, name, s, e, _ in spans:
            while stack and (stack[-1][0] <= s or stack[-1][0] < e):
                stack.pop()                  # closed before s, or not holding it
            row = out[name]
            row[0] += 1
            row[1] += e - s
            row[2] += e - s
            if stack:
                out[stack[-1][1]][2] -= e - s
            stack.append((e, name))
    return dict(out)


def sweep_us_per_op(tr: dict) -> float | None:
    """Σ self time of ``engine.sweep`` over Σ of its ``dispatched``
    counter, us: the engine's own scheduling time per op dispatched."""
    sweeps = _named(tr, "engine.sweep")
    ops = sum(st.get("dispatched", 0) for *_, st in sweeps)
    if not ops:
        return None
    return 1e-3 * self_times(tr)["engine.sweep"][2] / ops


def idle_by_span(tr: dict, devices) -> list[tuple[str, float]]:
    """The devices' idle time in the window (s, averaged over devices) by
    the innermost program span open in it, longest first; ``untraced``
    is the idle time under no program span."""
    lo, hi = tr["window"]
    spans = clipped(tr)
    # elementary segments between span boundaries, each owned by the open
    # span that started last
    bounds = sorted({lo, hi} | {t for sp in spans for t in (sp[2], sp[3])})
    starts = sorted(range(len(spans)), key=lambda i: spans[i][2])
    heap: list = []
    owners = []
    k = 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(starts) and spans[starts[k]][2] <= a:
            i = starts[k]
            heapq.heappush(heap, (-spans[i][2], -i))
            k += 1
        while heap and spans[-heap[0][1]][3] <= a:
            heapq.heappop(heap)
        owners.append((a, b, spans[-heap[0][1]][1] if heap else UNTRACED))
    by = defaultdict(float)
    for dev in devices:
        cover = trace_mod.union(trace_mod.clip(
            [(s, s + d) for dv, s, d in tr["device_ops"] if dv == dev], lo, hi))
        idle = trace_mod.gaps(cover, lo, hi)
        for a, b, name in owners:
            by[name] += trace_mod.overlap(idle, a, b)
    nd = max(len(devices), 1)
    return sorted(((n, t / nd / 1e9) for n, t in by.items()), key=lambda kv: -kv[1])


def report(tr: dict, chips: int) -> None:
    lo, hi = tr["window"]
    idle = idle_by_span(tr, range(chips))
    total = sum(t for _, t in idle)
    _say(f"program spans: device idle {total:.6f} s of a {(hi - lo) / 1e9:.6f} s window, "
         f"by the innermost program span open in it:")
    for name, t in idle:
        _say(f"  {name} {t:.6f} s ({100.0 * t / total if total else 0.0:.2f}%)")
    _say("program spans: count, time s, self time s:")
    rows = sorted(self_times(tr).items(), key=lambda kv: -kv[1][2])
    for name, (n, t, own) in rows:
        _say(f"  {name} {n} {t / 1e9:.6f} {own / 1e9:.6f}")


def _say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
