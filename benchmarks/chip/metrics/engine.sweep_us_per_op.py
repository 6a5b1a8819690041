"""The engine's own scheduling time per op it dispatched: the self time
of its ``engine.sweep`` spans (less the retirements, waits and op bodies
nested in them) over the ops they dispatched, us."""
from chipbench import spans


def read(ctx):
    tr = spans.of(ctx)
    return None if tr is None else spans.sweep_us_per_op(tr)
