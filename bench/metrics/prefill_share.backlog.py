"""Share of the device's busy time spent in the admission programs
(prompt prefill and its splice into the paged KV pool), from the trace;
0 when no request was admitted in the traced slice."""


def read(ctx):
    if ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * ctx.module_time(ctx.ADMIT)[1] / ctx.trace["busy_s"]
