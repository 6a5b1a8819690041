"""decode_attention's share of its roofline: one call per layer per
decode step of each group, over the live cache (bucket + steps so far)."""
from chipbench.counts import ACT_BYTES
from chipbench.roofline import share

KERNEL = "decode_attention"


def call(m: dict, batch: int, cache_len: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call: ``batch`` queries of all heads against
    ``cache_len`` live cache slots (k and v read once, q read and the
    output written)."""
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = 4 * batch * H * hd * cache_len
    nbytes = (2 * batch * cache_len * KV * hd + 2 * batch * H * hd) * ACT_BYTES
    return flops, nbytes


def read(ctx):
    m = ctx.model
    calls = [call(m, b, bucket + j)
             for rec in ctx.records for b, bucket, steps in rec.groups
             for j in range(1, steps + 1) for _ in range(m["n_layers"])]
    return share(ctx, KERNEL, calls)
