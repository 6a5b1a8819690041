"""The FLOPs of the prompts (every prompt token through every layer and
one head product per request) over the time from each wave's hand-over
to its last first token, times the chips' peak bf16 FLOP/s: the share of
the peak the prefill phase reaches, which bounds the first-token time."""


def read(ctx):
    flops = seconds = 0.0
    for rec in ctx.records:
        flops += sum(ctx.counts.request_flops(ctx.model, d.prompt_len, 1) for d in rec.requests)
        seconds += max(d.times[0] for d in rec.requests if d.times) - rec.handover
    if seconds <= 0:
        return None
    return 100.0 * flops / (seconds * ctx.chips * ctx.peaks["bf16_flops_per_s"])
