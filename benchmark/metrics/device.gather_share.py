"""Share of the device's busy time in the window spent in the gather
family's programs (``jit_tpq_gather*``: dictionary-index decode and
gathers), over the union of every device event, summed over chips
(profiler trace).  Nothing is read where no such program ran: a program
that does not name its plan programs by family."""

import window_trace


def read(ctx):
    t = window_trace.load(ctx)
    if t is None:
        return None
    fam, busy = t.module_s("jit_tpq_gather"), t.busy_s()
    if fam <= 0 or busy <= 0:
        return None
    return 100.0 * fam / busy
