"""ssd_scan's share of its roofline: one scan per layer per group
prefill, over the group's prompt bucket."""
from chipbench.counts import ACT_BYTES
from chipbench.roofline import share

KERNEL = "ssd_scan"


def call(m: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one scan over ``seq`` tokens: the recurrence's
    update and read-out per token; x, B, C and dt read once (dt in
    float32), y written once and the float32 final state written."""
    P, N = m["head_dim"], m["d_state"]
    H = m["expand"] * m["d_model"] // P
    flops = 4 * batch * seq * H * P * N
    nbytes = (2 * batch * seq * H * P + 2 * batch * seq * m["n_groups"] * N) * ACT_BYTES \
        + batch * seq * H * 4 + batch * H * P * N * 4
    return flops, nbytes


def read(ctx):
    m = ctx.model
    calls = [call(m, b, bucket)
             for rec in ctx.records for b, bucket, _ in rec.groups
             for _ in range(m["n_layers"])]
    return share(ctx, KERNEL, calls)
