"""Reduction of a profiler trace to device busy time, idle gaps, kernel
times and the operations that took the most time.

A trace is first brought to a plain form (`load_xplane`): device
operations ``[device, name, start_ns, dur_ns]`` from each TPU's
"XLA Ops" line, with the HLO text cut to ``"<op> <type> <opcode>"``, and
host events ``[thread, name, start_ns, dur_ns]``.  Both lie on the one
clock of the profile.  Everything after that is plain arithmetic over
intervals (`reduce`), checked by the tests on a window recorded on a
chip.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from collections import defaultdict

MARK_PREFIX = "chipbench."       # host spans the harness itself opens
_LAYOUT = re.compile(r"\{[^{}]*\}")
_SUFFIX = re.compile(r"\.\d+$")


def short_op(hlo: str) -> str:
    """``"%flash_attention.2 = bf16[32,16,1024,128]{...} custom-call(...)"``
    -> ``"flash_attention.2 bf16[32,16,1024,128] custom-call"``."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo.lstrip("%")
    if rest.startswith("("):                 # a tuple type: up to its match
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
    opcode = rest.split("(", 1)[0]
    return f"{name.lstrip('%')} {_LAYOUT.sub('', typ)} {opcode}"


def op_base(short: str) -> str:
    """The op's name without its ``.N`` suffix: ``flash_attention``."""
    return _SUFFIX.sub("", short.split(" ", 1)[0])


def is_kernel(short: str, kernel: str) -> bool:
    """Whether an op is a call of the named custom (Pallas) kernel."""
    return short.endswith(" custom-call") and op_base(short) == kernel


def load_xplane(trace_dir: str) -> dict:
    """The plain form of the one ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"{len(files)} xplane files under {trace_dir}")
    pd = ProfileData.from_file(files[0])
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.extend([dev, short_op(ev.name), ev.start_ns, ev.duration_ns]
                                  for ev in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend([line.name, ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events)
    return {"device_ops": device, "host": host}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def mark(trace: dict, name: str) -> tuple[float, float]:
    """(start, end) in ns of the harness's host span ``name``, the first
    one if it was opened more than once."""
    spans = [(s, s + d) for _, n, s, d in trace["host"] if n == name]
    if not spans:
        raise KeyError(f"no host span {name!r} in the trace")
    return min(spans)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of the (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(cover, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi) that the disjoint sorted ``cover`` leaves."""
    out, t = [], lo
    for s, e in cover:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(cover, s: float, e: float) -> float:
    """Length of [s, e) that the disjoint sorted ``cover`` holds."""
    i = max(bisect.bisect_right(cover, (s, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(cover) and cover[i][0] < e:
        total += max(0.0, min(e, cover[i][1]) - max(s, cover[i][0]))
        i += 1
    return total


def reduce(trace: dict, window: tuple[float, float], devices, kernels=()) -> dict:
    """Busy and idle time of ``devices`` in ``window`` (ns), per-kernel
    calls and device seconds, the ten ops with the most device time, and
    the idle time by what the host was doing, longest first."""
    lo, hi = window
    span = hi - lo
    ops = [(d, n, s, s + du) for d, n, s, du in trace["device_ops"]
           if d in devices and s + du > lo and s < hi]
    busy, per_op, idle_by = 0.0, defaultdict(float), defaultdict(float)
    kern = {k: [0, 0.0] for k in kernels}
    host = [(f"{t.rsplit('/', 1)[0] or 'host'}: {n}", max(s, lo), min(s + d, hi))
            for t, n, s, d in trace["host"]
            if s + d > lo and s < hi and not n.startswith(MARK_PREFIX)]
    host_cover = union((s, e) for _, s, e in host)
    for dev in devices:
        cover = union(clip([(s, e) for d, _, s, e in ops if d == dev], lo, hi))
        busy += sum(e - s for s, e in cover)
        idle = gaps(cover, lo, hi)
        # idle time by the host events running in it (nested events each
        # count), and the idle time in which no host event ran at all
        for n, s, e in host:
            idle_by[n] += overlap(idle, s, e)
        idle_by["no host event"] += sum(e - s for s, e in idle) - sum(
            overlap(idle, s, e) for s, e in host_cover)
    for d, n, s, e in ops:
        dur = min(e, hi) - max(s, lo)
        parts = n.split(" ")
        per_op[f"{op_base(n)} {parts[1] if len(parts) > 2 else ''}".strip()] += dur
        for k in kern:
            if is_kernel(n, k):
                kern[k][0] += 1
                kern[k][1] += (e - s) / 1e9
    nd = max(len(devices), 1)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": span / 1e9,
        "busy_s": busy / nd / 1e9,
        "kernels": {k: {"calls": c, "seconds": t} for k, (c, t) in kern.items()},
        "device_ops": [[n, t / nd / 1e9] for n, t in top],
        "idle_gaps": [[n, t / nd / 1e9] for n, t in idle],
    }
