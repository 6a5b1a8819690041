"""The forward FLOPs that the traced window's served tokens required
(prompts, fed-back tokens, head products; the benchmark's own count)
over the window's seconds times the chips' peak bf16 FLOP/s."""


def read(ctx):
    flops = sum(ctx.counts.request_flops(ctx.model, d.prompt_len, len(d.tokens))
                for rec in ctx.records for d in rec.requests)
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
