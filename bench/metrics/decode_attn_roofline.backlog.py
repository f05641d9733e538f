"""The Pallas paged decode-attention kernel's share of its roofline: the
least time in which the chip could read the K/V of every active lane's
valid tokens (or do their score and value products, if that is longer),
over the kernel's device time in the traced slice."""


def read(ctx):
    n, seconds = ctx.op_time(ctx.DECODE_ATTN)
    if n == 0 or not ctx.steps:
        return None
    nbytes = ops = 0
    for lanes in ctx.steps:
        b, f = ctx.flops.decode_attn_cost(ctx.config, lanes, ctx.kv_itemsize)
        nbytes, ops = nbytes + b, ops + f
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                ops / ctx.peaks["flops_bf16"])
    return 100.0 * least / seconds
