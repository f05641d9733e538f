"""Share of the traced slice in which the device sat idle between two
operations with no scheduler span open on the host (the harness's own
code, or the engine's outside the scheduler): ``bench/spans.py``."""
import spans


def read(ctx):
    return spans.idle_share(ctx, "untraced")
