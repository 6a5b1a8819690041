"""A kernel's share of its roofline over a traced window: the least time
the chip could take for every call the window made (the larger of FLOPs
over peak and bytes over bandwidth, from the call shapes), over the
kernel's device time in the trace.  Nothing is read when the trace holds
no call of the kernel, or not the number of calls the window made."""
from __future__ import annotations

import sys

from .counts import least_time_s


def share(ctx, kernel: str, calls) -> float | None:
    """``calls``: one (FLOPs, bytes) per call the window made."""
    k = ctx.trace["kernels"].get(kernel, {"calls": 0, "seconds": 0.0})
    if k["calls"] == 0 or k["seconds"] <= 0:
        return None
    if k["calls"] != len(calls):
        print(f"{kernel}: {k['calls']} calls in the trace, {len(calls)} made; "
              f"its roofline is not read", file=sys.stderr)
        return None
    least = sum(least_time_s(f, b, ctx.peaks) for f, b in calls)
    return 100.0 * least / k["seconds"]
