"""Share of the device's busy time in the traced window that went to the
``XLA Ops`` events matching ``pattern``, which is searched in an event's
short name (``trace_reduce.short``: opcode first, as in ``copy copy
bf16[513,20,16,64]``, so ``^copy `` takes the copies): 100 x their device
seconds over ``busy_s``. A share of busy time, not of a peak. No trace or
no match: nothing returned, never 0."""


def reduce(args, facts, trace):
    if trace is None or not trace.ops:
        return None
    secs, busy = trace.seconds_matching(args["pattern"]), trace.busy_s()
    return 100.0 * secs / busy if secs and busy else None
