"""Share of the traced slice in which the device sat idle between two
operations while the host was in another scheduler phase (a ``sched.*``
span open, none of them ``sched.admit``): ``bench/spans.py``."""
import spans


def read(ctx):
    return spans.idle_share(ctx, "tick")
