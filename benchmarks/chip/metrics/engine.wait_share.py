"""Share of the engines' running time (``serve.engine.start`` to
``.end``) the engine thread spent blocked in ``engine.wait``: on a
worker's op body or on the device."""
from chipbench import spans


def read(ctx):
    tr = spans.of(ctx)
    return None if tr is None else spans.wait_share(tr)
