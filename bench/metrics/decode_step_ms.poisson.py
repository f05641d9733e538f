"""Device time of one decode-step program, averaged over its executions
in the traced slice."""


def read(ctx):
    n, seconds = ctx.module_time(ctx.STEP)
    return 1e3 * seconds / n if n else None
