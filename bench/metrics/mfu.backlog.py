"""The whole step's share of the chip's peak: operations that the prefill
and decode work dispatched in the traced slice needs (the counts of the
model's family, ``bench/families/<model_type>.py``), over the slice's
length times the peak bf16 rate."""


def read(ctx):
    f = ctx.flops
    ops = sum(f.decode_flops(ctx.config, lanes) for lanes in ctx.steps) \
        + sum(f.prefill_flops(ctx.config, p) for p in ctx.admissions)
    if ops == 0:
        return None
    return 100.0 * ops / (ctx.trace["window_s"] * ctx.peaks["flops_bf16"])
