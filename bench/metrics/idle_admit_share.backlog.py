"""Share of the traced slice in which the device sat idle between two
operations while the host was admitting a request (a ``sched.admit``
span open, or one of its children): ``bench/spans.py``."""
import spans


def read(ctx):
    return spans.idle_share(ctx, "admit")
