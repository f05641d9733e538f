"""The grouped expert kernel's share of its roofline: the least time in
which the chip could do the held experts' work of every layer call in the
traced slice (the family's ``moe_cost``: each held expert's three
matrices read once a call and the routed rows' inputs and outputs, at
819 GB/s, or the rows' SwiGLU products at 197 TFLOP/s if that is longer;
a call's rows are its tokens x experts per token x held / bank), over the
device time of the kernel's operations (``%grouped_expert_ffn.*``)."""

KERNEL = "grouped_expert_ffn"


def read(ctx):
    n, seconds = ctx.op_time(KERNEL)
    f, m = ctx.flops, ctx.config
    if n == 0 or not hasattr(f, "moe_cost"):
        return None
    calls = [len(lanes) for lanes in ctx.steps] + list(ctx.admissions)
    least = 0.0
    for tokens in calls:
        nbytes, ops = f.moe_cost(m, f.moe_rows(m, tokens))
        least += max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                     ops / ctx.peaks["flops_bf16"])
    return 100.0 * m["num_hidden_layers"] * least / seconds
