"""Host wall time the engine spends inside one stage op's body (the
dispatch of its program), averaged over every op of the traced waves:
the program's own per-stage dispatch seconds over its op count."""


def read(ctx):
    firings = sum(rec.firings for rec in ctx.records)
    if not firings:
        return None
    return 1e6 * sum(rec.dispatch_s for rec in ctx.records) / firings
