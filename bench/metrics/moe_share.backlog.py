"""Share of the device's busy time in the grouped expert kernel's
operations (``%grouped_expert_ffn.*``), from the trace."""

KERNEL = "grouped_expert_ffn"


def read(ctx):
    n, seconds = ctx.op_time(KERNEL)
    if n == 0 or ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * seconds / ctx.trace["busy_s"]
