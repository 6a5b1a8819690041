"""flash_attention's share of its roofline: one causal call per layer per
group prefill, over the group's prompt bucket."""
from chipbench.counts import ACT_BYTES
from chipbench.roofline import share

KERNEL = "flash_attention"


def call(m: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one causal call over ``seq`` tokens: the causal
    half of the score matrix, q/k/v read once and the output written
    once."""
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = 4 * batch * H * hd * seq * (seq + 1) / 2
    nbytes = batch * seq * (2 * H + 2 * KV) * hd * ACT_BYTES
    return flops, nbytes


def read(ctx):
    m = ctx.model
    calls = [call(m, b, bucket)
             for rec in ctx.records for b, bucket, _ in rec.groups
             for _ in range(m["n_layers"])]
    return share(ctx, KERNEL, calls)
