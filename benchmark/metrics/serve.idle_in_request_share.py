"""Share of the window in which the device is idle while at least one
request is executing in the service (a ``tpq.serve.request`` span open on
the host), averaged over the chips (profiler trace).  Nothing is read where
the trace holds no such span: a program that does not annotate its
requests."""

import window_trace


def read(ctx):
    t = window_trace.load(ctx)
    if t is None or t.window_s <= 0:
        return None
    idle = t.idle_within_s("tpq.serve.request")
    if idle is None:
        return None
    return 100.0 * idle / (t.window_s * len(t.chips))
