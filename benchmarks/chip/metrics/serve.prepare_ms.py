"""Time a serve spends before its engine starts (grouping, bucketing,
the plan's preflight, the warm-shape check, building the engine): the
mean ``serve.prepare`` span the program writes, one per wave, ms."""
from chipbench import spans


def read(ctx):
    tr = spans.of(ctx)
    return None if tr is None else spans.prepare_ms(tr)
