"""Stage programs one token step dispatches: the pipeline's stages as
placed on the chip (embed, block stages, head, after any fusion)."""


def read(ctx):
    return ctx.stage_count
