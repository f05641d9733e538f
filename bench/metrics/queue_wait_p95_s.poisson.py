"""95th percentile of the wait from a request's due time to the tick that
admitted it, over the requests due in the window (host clock)."""

import numpy as np


def read(ctx):
    waits = [ctx.stamps[u]["admit"] - ctx.stamps[u]["due"]
             for u in ctx.window["in_window"] if "admit" in ctx.stamps[u]]
    return float(np.percentile(waits, 95)) if waits else None
