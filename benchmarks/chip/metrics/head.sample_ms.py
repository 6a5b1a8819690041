"""Time of one head retirement's sampling round trip: the mean
``head.sample`` span (the sampler's dispatch and the copy of the
sampled ids to the host), ms."""
from chipbench import spans


def read(ctx):
    tr = spans.of(ctx)
    return None if tr is None else spans.sample_ms(tr)
