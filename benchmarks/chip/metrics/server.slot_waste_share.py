"""Share of the token slots the serving groups executed that carried no
real token: a group prefills batch x bucket slots and decodes batch
slots per step until its longest budget is spent.  Real are the prompt
tokens and each generated token but the first (which the prefill
produced); read from the groups' shapes and the served lengths."""


def read(ctx):
    slots = real = 0
    for rec in ctx.records:
        slots += sum(b * bucket + b * steps for b, bucket, steps in rec.groups)
        real += sum(d.prompt_len + max(len(d.tokens) - 1, 0) for d in rec.requests)
    return 100.0 * (1.0 - real / slots) if slots else None
