"""Host time of one admission: a ``sched.admit`` span's length less the
prefill and suffix-prefill dispatches inside it, averaged over the
slice's admissions (``bench/spans.py``); nothing when none fell in it."""
import spans


def read(ctx):
    got = spans.attribution(ctx)
    if got is None or not got["admit_host_ms"]:
        return None
    return sum(got["admit_host_ms"]) / len(got["admit_host_ms"])
